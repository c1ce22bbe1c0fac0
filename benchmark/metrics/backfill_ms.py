"""The backfill action per traced session, per-action or the fused chain's
replay stage: the program's vt.action.backfill span.
None where the program records no such span."""

import progspans


def read(run):
    return progspans.read(run, "vt.action.backfill")
