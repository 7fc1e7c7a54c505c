"""Share of the traced window in which the device is idle while the host
is inside a step's launch (``prefill.launch`` or ``decode.launch``), in
%."""
from chipbench import spans


def read(run):
    return spans.idle_in_launch_pct(run)
