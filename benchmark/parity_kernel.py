"""What the reliability tier's parity kernel needs: the bytes of one
``fec_parity_window_step`` execution as a function of the shapes the
trace spells — the row ``benchmark/kernels.py``'s table lacks, which
``readers/trace_op_parity.py`` adds to it.

One execution reads the window's rows ``u8[K, B]`` and its coefficients
``u8[R, K]``, writes the parity ``u8[R, B]``, and gathers from the two
GF(256) tables, log ``s32[256]`` and antilog ``s32[512]``."""

from __future__ import annotations

MODULE = "fec_parity_window_step"
TABLE_BYTES = (256 + 512) * 4
#: a window's byte pad starts here (``relay.fec.PARITY_MIN_BYTES``)
MIN_BYTES = 256


def fec_parity_window_step_bytes(k: int, b: int, r: int) -> int:
    return k * b + r * k + r * b + TABLE_BYTES


def parity_shape(shapes: dict) -> tuple[int, int, int] | None:
    """(K, B, R) of one program from the shapes its ops name: B is the
    widest byte axis, K the rows' height (the coefficients' width) and
    R the parity rows'.  None where the trace does not spell them."""
    two = [d for d in shapes.get("u8", []) if len(d) == 2]
    if not two:
        return None
    b = max(d[1] for d in two)
    if b < MIN_BYTES:
        return None
    heights = {d[0] for d in two if d[1] == b}
    for r, k in (d for d in two if d[1] < b):
        if k in heights and r <= k:
            return k, b, r
    for dims in (d for ds in shapes.values() for d in ds if len(d) == 3):
        if dims[2] == b and dims[1] in heights:
            return dims[1], b, dims[0]
    return None
