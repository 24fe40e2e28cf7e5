"""``trace_op`` for the parity kernel, ``fec_parity_window_step``, whose
row (``benchmark/parity_kernel.py``) ``benchmark/kernels.py``'s table
does not hold yet: it adds that row, then reads as ``trace_op`` does.
Once the table holds the row, the metric names ``trace_op`` and this
reader and ``parity_kernel.py`` go (PERF.md section 7)."""

from .. import kernels, parity_kernel
from . import trace_op

kernels.KERNELS.setdefault(parity_kernel.MODULE, (
    parity_kernel.parity_shape, parity_kernel.fec_parity_window_step_bytes))

read = trace_op.read
