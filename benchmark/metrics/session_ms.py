"""Wall time of the window's sessions (load conf, open, actions, close, each
fenced by a device drain), summed and divided by their count."""


def read(run):
    if not run.sessions:
        return None
    return sum(s["total_s"] for s in run.sessions) / len(run.sessions) * 1e3
