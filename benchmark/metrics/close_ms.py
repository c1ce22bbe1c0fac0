"""Mean host time per session of the harness's span around the close step."""


def read(run):
    if not run.sessions:
        return None
    return sum(s["close_s"] for s in run.sessions) / len(run.sessions) * 1e3
