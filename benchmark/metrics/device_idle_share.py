"""Share of the traced sessions' span time in which no device operation
ran: 1 - busy / span, summed over the traced sessions, in percent."""


def read(run):
    if not run.traced:
        return None
    span = sum(s for s, _ in run.traced)
    busy = sum(b for _, b in run.traced)
    return 100.0 * (1.0 - busy / span) if span else None
