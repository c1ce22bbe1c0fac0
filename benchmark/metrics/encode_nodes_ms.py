"""The encoder's node matrices (idle, used, allocatable) and its per-node
int32 bound check per traced session: the program's vt.encode.nodes spans,
inside vt.encode (ops/encoder.py). None where the program records no such
span."""

import progspans


def read(run):
    return progspans.read(run, "vt.encode.nodes")
