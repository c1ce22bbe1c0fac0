"""Encode and host-to-device staging per device-path session: the tpuscore
profile's encode_s + pack_s + h2d_s (ops/encoder, replica, shard,
solver._pack/_stage). Sessions that stayed on the serial path have none."""


def read(run):
    vals = [sum(s["profile"].get(k, 0.0) for k in ("encode_s", "pack_s", "h2d_s"))
            for s in run.sessions if s["profile"].get("mode") == "rounds"]
    return sum(vals) / len(vals) * 1e3 if vals else None
