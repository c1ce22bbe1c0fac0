"""Mean host time per session of the harness's span around the open step."""


def read(run):
    if not run.sessions:
        return None
    return sum(s["open_s"] for s in run.sessions) / len(run.sessions) * 1e3
