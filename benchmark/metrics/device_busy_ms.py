"""Device busy time per traced session: the union of device-operation
intervals inside each bench.session span (devtrace.session_busy)."""


def read(run):
    if not run.traced:
        return None
    return sum(b for _, b in run.traced) / len(run.traced) / 1e6
