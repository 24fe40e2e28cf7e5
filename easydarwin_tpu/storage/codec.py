"""GF(256) stripe codec: spill window blobs → k data + m parity shards.

The erasure math is the PR 11 reliability tier's, reused verbatim: a
stripe is ``k`` consecutive spill-window blobs of one track, zero-padded
on the byte axis to the widest blob, and the ``m`` parity shards are the
Vandermonde rows ``C[p, i] = α^(i·p)`` (``relay.fec.coeff_rows`` over
deltas ``0..k-1``) matmul'd against that ``[k, B]`` matrix.  The matmul
runs on the device (``models.relay_pipeline.fec_parity_window_step`` —
the SAME jitted kernel that computes wire FEC parity) and every row is
compared against the independent host oracle ``relay.fec.gf_matmul``
through the ``_install_segment`` discipline: a mismatch counts
``fec_parity_oracle_mismatch_total``, latches this codec onto host
parity and emits one ``storage.host_fallback`` — a kernel bug degrades
the tier to host math, it never persists an unchecked byte.

Reconstruction is the receiver path's Gaussian solve: XOR the surviving
data rows' contributions out of the surviving parity rows (syndromes),
then ``gf_solve`` the Vandermonde subsystem for the missing rows —
preferring the LOWEST parity indices, which form a true Vandermonde
system and always solve.  More than ``m`` missing shards, or a singular
arbitrary-index subset, raises :class:`StorageError` and counts
``storage_reconstructs_total{result="failed"}`` — a read that cannot be
byte-exact fails loudly, never silently partial.
"""

from __future__ import annotations

import time

import numpy as np

from .. import obs
from ..relay.fec import coeff_for_indices, coeff_rows, gf_matmul, gf_solve


class StorageError(RuntimeError):
    """A stripe that cannot be encoded or byte-exactly reconstructed."""


class StripeCodec:
    """Encode/reconstruct one ``k + m`` stripe of window blobs."""

    def __init__(self, k: int, m: int, *, use_device: bool = True):
        if not (1 <= k and 1 <= m <= 8):
            raise ValueError(f"bad stripe geometry k={k} m={m}")
        self.k = int(k)
        self.m = int(m)
        self.use_device = bool(use_device)
        #: latched on the first device/oracle divergence: host parity
        #: from then on (same semantics as StreamFec.host_fallback)
        self.host_fallback = False
        self.oracle_mismatches = 0
        self.device_passes = 0

    # ------------------------------------------------------------- encode
    def parity(self, blobs: list[bytes]) -> list[bytes]:
        """The ``m`` parity shard payloads over ``k`` data blobs (short
        stripes pad with ``b""`` entries).  Each payload is the stripe
        width ``B = max(len(blob))`` — the padded region's parity is
        zero by construction (gf_mul(0, ·) = 0), so trimming is free."""
        if len(blobs) != self.k:
            raise StorageError(
                f"stripe wants {self.k} blobs, got {len(blobs)}")
        from ..ops.staging import pow2
        width = max([len(b) for b in blobs] + [1])
        b_pad = pow2(width, 256)
        rows = np.zeros((self.k, b_pad), np.uint8)
        for i, b in enumerate(blobs):
            if b:
                rows[i, :len(b)] = np.frombuffer(b, np.uint8)
        r_pad = pow2(self.m, 1)
        coeff = coeff_rows(range(self.k), r_pad)
        host = gf_matmul(coeff, rows)
        parity = host
        if self.use_device and not self.host_fallback:
            dev = None
            try:
                from ..models.relay_pipeline import fec_parity_window_step
                t0 = time.perf_counter_ns()
                dev = np.asarray(fec_parity_window_step(rows, coeff))
                obs.TPU_PASS_SECONDS.observe(
                    (time.perf_counter_ns() - t0) / 1e9,
                    stage="storage_parity")
                obs.TPU_H2D_BYTES.inc(rows.nbytes + coeff.nbytes)
                obs.TPU_D2H_BYTES.inc(dev.nbytes)
                self.device_passes += 1
            except Exception as e:
                # host parity (already computed above) serves this
                # stripe; counted + logged, never silent
                from ..device import note_swallowed
                note_swallowed("storage_parity", e)
                dev = None
            if dev is not None and not np.array_equal(dev, host):
                # the _install_segment discipline: count, discard the
                # device result, latch host parity — never persist an
                # unchecked row
                self.oracle_mismatches += 1
                obs.FEC_PARITY_ORACLE_MISMATCH.inc()
                if not self.host_fallback:
                    self.host_fallback = True
                    obs.EVENTS.emit("storage.host_fallback", level="warn",
                                    mismatches=self.oracle_mismatches)
            elif dev is not None:
                parity = dev
        return [parity[p, :width].tobytes() for p in range(self.m)]

    # -------------------------------------------------------- reconstruct
    def reconstruct(self, present: dict[int, bytes], lens: list[int], *,
                    asset: str = "?",
                    crcs: list[int] | None = None) -> dict[int, bytes]:
        """Byte-exact blobs for every MISSING data index of one stripe.

        ``present`` maps shard index → payload: every surviving data
        shard (``idx < k``, exact blob bytes) plus surviving parity rows
        (``idx >= k``, stripe-width bytes).  ``lens`` are the k data
        blob lengths from the manifest.  Returns ``{data_idx: blob}``
        for each missing index; raises :class:`StorageError` (and
        counts the failure) when more than the surviving parity can
        solve, or the chosen coefficient subset is singular.

        The wide math is ONE matmul: invert the tiny ``[n, n]``
        Vandermonde subsystem (``gf_solve`` against I — eliminating the
        stripe-width rows directly costs ~2·n² scalar row ops over B
        bytes each), fold the inverse into a combined coefficient
        matrix over the stacked survivor rows, and apply it.  When
        ``crcs`` (the manifest's per-window crc32s) are given and the
        device is healthy, that matmul runs on the SAME jitted kernel
        that writes parity, oracle-checked end-to-end against the
        manifest crc32s: a mismatch counts, latches host fallback and
        recomputes with host math — the exact ``parity()`` discipline
        with the crc as the independent check."""
        k = self.k
        if len(lens) != k:
            raise StorageError(f"{asset}: manifest lens {len(lens)} != k")
        missing = [i for i in range(k) if i not in present]
        need = [i for i in missing if lens[i] > 0]
        out = {i: b"" for i in missing if lens[i] == 0}
        if not need:
            return out
        pav = sorted(i - k for i in present if i >= k)
        if len(need) > len(pav):
            obs.STORAGE_RECONSTRUCTS.inc(result="failed")
            obs.EVENTS.emit("storage.reconstruct", level="error",
                            asset=asset, missing=len(need),
                            parity=len(pav))
            raise StorageError(
                f"{asset}: {len(need)} data shards missing, only "
                f"{len(pav)} parity rows survive")
        # LOWEST surviving parity indices first: consecutive-from-0 rows
        # form a true Vandermonde system (always solvable); an arbitrary
        # subset can be singular, which gf_solve counts and reports
        n = len(need)
        idxs = pav[:n]
        ainv = gf_solve(coeff_for_indices(need, idxs),
                        np.eye(n, dtype=np.uint8), caller="storage")
        if ainv is None:
            obs.STORAGE_RECONSTRUCTS.inc(result="failed")
            obs.EVENTS.emit("storage.solve_singular", level="error",
                            asset=asset, missing=len(need))
            raise StorageError(
                f"{asset}: singular parity subset {idxs} for {need}")
        # stacked survivors [chosen parity rows ∥ surviving data rows];
        # D_need = A⁻¹·P ⊕ A⁻¹·C_known·D_known = [A⁻¹ | A⁻¹·C_k]·stack
        width = max([len(v) for i, v in present.items() if i >= k]
                    + [max(lens)])
        known = [i for i in range(k) if i in present and lens[i] > 0]
        ccomb = ainv
        if known:
            ccomb = np.concatenate(
                [ainv, gf_matmul(ainv, coeff_for_indices(known, idxs))],
                axis=1)
        bufs = [present[p + k] for p in idxs] \
            + [present[i] for i in known]
        if int(ccomb.max(initial=0)) <= 1:
            # single-loss stripes solve through parity row 0 — the XOR
            # row — so every combined coefficient is 0/1 and the apply
            # is pure XOR straight over the survivor buffers (RAID-5's
            # fast path): no stacked matrix, no table gathers
            solved = np.zeros((n, width), np.uint8)
            for r in range(n):
                for i in np.flatnonzero(ccomb[r]):
                    b = bufs[i]
                    solved[r, :len(b)] ^= np.frombuffer(b, np.uint8)
        else:
            surv = np.zeros((len(bufs), width), np.uint8)
            for j, b in enumerate(bufs):
                surv[j, :len(b)] = np.frombuffer(b, np.uint8)
            solved = self._wide_matmul(ccomb, surv, need, lens, crcs)
        for j, i in enumerate(need):
            out[i] = solved[j, :lens[i]].tobytes()
        obs.STORAGE_RECONSTRUCTS.inc(result="ok")
        obs.EVENTS.emit("storage.reconstruct", asset=asset,
                        missing=len(need))
        return out

    def _wide_matmul(self, ccomb: np.ndarray, surv: np.ndarray,
                     need: list[int], lens: list[int],
                     crcs: list[int] | None) -> np.ndarray:
        """``ccomb × surv`` on the device when the manifest crc32s can
        oracle-check the result; host ``gf_matmul`` otherwise (and on
        any divergence, with the parity-path mismatch accounting)."""
        if not (self.use_device and not self.host_fallback and crcs):
            return gf_matmul(ccomb, surv)
        import zlib
        from ..ops.staging import pow2
        dev = None
        try:
            from ..models.relay_pipeline import fec_parity_window_step
            rows = np.zeros((pow2(surv.shape[0], 1),
                             pow2(surv.shape[1], 256)), np.uint8)
            rows[:surv.shape[0], :surv.shape[1]] = surv
            coeff = np.zeros((pow2(ccomb.shape[0], 1), rows.shape[0]),
                             np.uint8)
            coeff[:ccomb.shape[0], :ccomb.shape[1]] = ccomb
            t0 = time.perf_counter_ns()
            dev = np.asarray(fec_parity_window_step(rows, coeff))
            obs.TPU_PASS_SECONDS.observe(
                (time.perf_counter_ns() - t0) / 1e9,
                stage="storage_reconstruct")
            obs.TPU_H2D_BYTES.inc(rows.nbytes + coeff.nbytes)
            obs.TPU_D2H_BYTES.inc(dev.nbytes)
        except Exception:
            dev = None                   # no backend: host math serves
        if dev is not None:
            ok = all((zlib.crc32(dev[j, :lens[i]].tobytes())
                      & 0xFFFFFFFF) == int(crcs[i])
                     for j, i in enumerate(need))
            if ok:
                self.device_passes += 1
                return dev[:, :surv.shape[1]]
            self.oracle_mismatches += 1
            obs.FEC_PARITY_ORACLE_MISMATCH.inc()
            if not self.host_fallback:
                self.host_fallback = True
                obs.EVENTS.emit("storage.host_fallback", level="warn",
                                mismatches=self.oracle_mismatches)
        return gf_matmul(ccomb, surv)


__all__ = ["StripeCodec", "StorageError"]
