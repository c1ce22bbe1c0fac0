"""Host time blocked on the device inside the actions per traced session:
the program's vt.device.wait spans (utils/devprof fetch waits and fences)
between the harness's t1 and t2.
None where the program records no such span."""

import progspans


def read(run):
    return progspans.read(run, "vt.device.wait", "actions")
