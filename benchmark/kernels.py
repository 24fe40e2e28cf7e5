"""What a kernel's shapes need: operations and bytes as functions of the
program's name and shapes, whatever implements it."""

from __future__ import annotations

STATE_COLS = 6          # uint32 columns of a subscriber's output state
ROW_BYTES = 100         # a staged window row: 96 header bytes + 4


def megabatch_window_step_bytes(b: int, p: int, s: int) -> int:
    """``window`` [B, P, 100] u8 in, ``out_state`` [B, S, 6] u32 in,
    packed egress params [B, 4S + 1] u32 out."""
    return b * p * ROW_BYTES + b * s * STATE_COLS * 4 + b * (4 * s + 1) * 4


def megabatch_shape(shapes: dict) -> tuple[int, int, int] | None:
    """(B, P, S) of one ``megabatch_window_step`` program from the shapes
    its ops name: the staged window ``u8[B, P, 100]`` and the output
    state ``u32[B, S, 6]``."""
    win = [d for d in shapes.get("u8", []) if len(d) == 3
           and d[2] == ROW_BYTES]
    st = [d for d in shapes.get("u32", []) if len(d) == 3
          and d[2] == STATE_COLS]
    if not win or not st or win[0][0] != st[0][0]:
        return None
    return win[0][0], win[0][1], st[0][1]


#: the parity kernel's two GF(256) tables, log ``s32[256]`` and antilog
#: ``s32[512]``, gathered from in every execution
PARITY_TABLE_BYTES = (256 + 512) * 4
#: a window's byte pad starts here (``relay.fec.PARITY_MIN_BYTES``)
PARITY_MIN_BYTES = 256


def fec_parity_window_step_bytes(k: int, b: int, r: int) -> int:
    """The window's rows ``u8[K, B]`` and coefficients ``u8[R, K]`` in,
    the parity ``u8[R, B]`` out, and the two tables."""
    return k * b + r * k + r * b + PARITY_TABLE_BYTES


def parity_shape(shapes: dict) -> tuple[int, int, int] | None:
    """(K, B, R) of one ``fec_parity_window_step`` program from the
    shapes its ops name: B is the widest byte axis, K the rows' height
    (the coefficients' width) and R the parity rows'.  None where the
    trace does not spell them."""
    two = [d for d in shapes.get("u8", []) if len(d) == 2]
    if not two:
        return None
    b = max(d[1] for d in two)
    if b < PARITY_MIN_BYTES:
        return None
    heights = {d[0] for d in two if d[1] == b}
    for r, k in (d for d in two if d[1] < b):
        if k in heights and r <= k:
            return k, b, r
    for dims in (d for ds in shapes.values() for d in ds if len(d) == 3):
        if dims[2] == b and dims[1] in heights:
            return dims[1], b, dims[0]
    return None


#: program name -> (its shape from the trace's op names, the bytes that
#: shape needs); a new kernel's roofline is one more row
KERNELS = {"megabatch_window_step": (megabatch_shape,
                                     megabatch_window_step_bytes),
           "fec_parity_window_step": (parity_shape,
                                      fec_parity_window_step_bytes)}


def least_seconds(module: str, mod: dict, peaks: dict, bound: str
                  ) -> float | None:
    """The least time the chip could take for every traced execution of
    ``module``: what its shapes need over the device's peak.  A program
    whose shapes the trace does not spell gives nothing to read."""
    if module not in KERNELS:
        return None
    shape_of, need = KERNELS[module]
    total = 0
    for prog in mod.get("programs", {}).values():
        shape = shape_of(prog.get("shapes", {}))
        if shape is None:
            return None
        total += prog["count"] * need(*shape)
    return total / peaks[bound] if total else None
