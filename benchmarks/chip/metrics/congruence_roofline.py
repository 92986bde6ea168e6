"""The fused congruence kernel's share of its roofline, in percent.

The least time is the larger of the operations over the chip's peak rate
and the bytes over its HBM bandwidth, for the work the cell's check
demands (``roofline.py``), per chip; it is divided by the summed device
time of the kernel's events in the trace, averaged over the chips.
"""

import re
import sys

import roofline

#: The fused Pallas congruence kernel on the device: a Mosaic custom call
#: whose output stacks the pass's 8 rows, ``f32[8, apps, variants]``.
KERNEL = re.compile(r"= f32\[8,\d+,\d+\]\{[^}]*\} custom-call\(.*"
                    r'custom_call_target="tpu_custom_call"')


def read(ctx):
    work = ctx.cell.kernel_work(ctx.window.ops)
    seconds = ctx.trace.op_seconds(lambda name: bool(KERNEL.search(name)))
    if work is None or not seconds:
        return None
    chips = len(ctx.run.devices)
    least, bound = roofline.least_seconds(work[0] / chips, work[1] / chips,
                                          ctx.run.devices[0].device_kind)
    print(f"congruence_roofline: {bound}-bound, least {least!r} s against "
          f"{seconds!r} s of kernel time", file=sys.stderr)
    return 100.0 * least / seconds
