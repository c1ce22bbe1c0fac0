"""Apply per device-path session: the tpuscore profile's apply_s (bulk
placement writeback) plus each evict stage's apply_s (op-log replay)."""


def read(run):
    vals = []
    for s in run.sessions:
        p = s["profile"]
        if p.get("mode") != "rounds":
            continue
        vals.append(p.get("apply_s", 0.0) + sum(
            v.get("apply_s", 0.0) for k, v in p.items()
            if k.startswith("evict_") and isinstance(v, dict)))
    return sum(vals) / len(vals) * 1e3 if vals else None
