"""Rounds of the rounds kernel (ops/rounds.py) per device-path session of
the window: the tpuscore profile's ``rounds``. Each round is a pass of
scoring, nomination, conflict resolution and commit on the device.
Sessions that stayed on the serial path have none."""


def read(run):
    vals = [s["profile"]["rounds"] for s in run.sessions
            if s["profile"].get("mode") == "rounds"
            and "rounds" in s["profile"]]
    return sum(vals) / len(vals) if vals else None
