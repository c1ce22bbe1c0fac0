"""Mean host time per session of the harness's span around the actions step."""


def read(run):
    if not run.sessions:
        return None
    return sum(s["actions_s"] for s in run.sessions) / len(run.sessions) * 1e3
