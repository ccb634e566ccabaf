"""fused_rows_roofline (kernels, kernels/fused_erm.py): the least time the
fused per-row gradient kernel's calls need at the chip's peaks over their
summed device time in the profiler trace, in %.  A call computes one
scattered batch's data gradient: it must read b rows of n features and a
label, and w, and write g, and do 4bn FLOPs (harness/work.py).  Bytes
bound it.  The 8-row groups the kernel really moves are not required work
(``row_dma_fill_share`` reads how much of them is)."""
from harness import work

# names the rows kernel's calls may carry in the device trace: the HLO
# instruction of its custom call (``fused_grad_rows.<n>``) or the Mosaic
# kernel's own name
KERNEL = ("fused_grad_rows", "_rows_kernel")


def read(rec):
    if rec.device is None or rec.peaks is None:
        return None
    calls, secs = rec.device.kernel(KERNEL)
    if not calls:
        return None
    meth, n = rec.cell.config["method"], rec.cell.config["corpus"]["features"]
    per_call = (work.dense_batch(meth["batch_size"], n)
                + work.Work(0.0, 2.0 * n * work.F32))
    return 100.0 * calls * per_call.seconds(rec.peaks) / secs
