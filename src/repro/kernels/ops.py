"""The kernel plane: backend dispatch for the engine's Pallas hot paths.

The engine tick has three inner loops hot enough to fuse (ROADMAP "fast as
the hardware allows"): per-key CAS arbitration, the MVCC Cond R1/R2 version
pick, and the doorbell-batched multi-array row gather.  Each has a Pallas
kernel (lock_arbiter / mvcc_version_select / multi_read) and a pure-jnp
reference implementation; THIS module owns the choice between them.

A *kernel plane* is one of

  * ``"jnp"``            — the reference gather/scatter path (always available)
  * ``"pallas"``         — compiled Pallas kernels (TPU/GPU)
  * ``"pallas_interpret"`` — the same kernels in interpret mode (CPU CI:
    exercises the kernel code paths without a TPU)

``"auto"`` resolves per backend at plan time: Pallas on TPU/GPU, jnp on
CPU.  The plane threads through ``ExperimentSpec.kernel_plane`` ->
``GridSpec`` -> ``EngineConfig.kernel_plane`` as a STATIC field, so it is
part of the compiled program identity and never traced.

Parity contract (DESIGN.md §9, pinned by tests/test_kernel_parity.py and
the kernel-parity CI job): for every protocol, integer counters under a
Pallas plane are bitwise-equal to the jnp plane.  The kernels therefore
implement *exactly* the reference semantics — lexicographic-min
arbitration with no index tiebreak, and row gathers made of DMA copies
and an int32 lane select (never an f32 MXU matmul).

The LM stack's flash-attention wrapper (`attention_op`) also lives here:
same backend detection, cfg-level opt-in from models/lm.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.arbiter import scatter_min_winner
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lock_arbiter import lock_arbiter
from repro.kernels.multi_read import multi_read
from repro.kernels.mvcc_version_select import mvcc_version_select

JNP = "jnp"
PALLAS = "pallas"
PALLAS_INTERPRET = "pallas_interpret"
KERNEL_PLANES = (JNP, PALLAS, PALLAS_INTERPRET)
AUTO = "auto"


def _accel() -> bool:
    return jax.default_backend() in ("tpu", "gpu")


def default_interpret() -> bool:
    """Backend-detected ``interpret`` default for the raw kernel entry
    points (kernels must not hardcode it in their signatures)."""
    return not _accel()


def default_plane() -> str:
    """What ``"auto"`` resolves to on this process's default backend."""
    return PALLAS if _accel() else JNP


def resolve_plane(plane: str | None) -> str:
    """Validate/resolve a kernel-plane knob (``None``/"auto" -> backend)."""
    if plane is None or plane == AUTO:
        return default_plane()
    if plane not in KERNEL_PLANES:
        raise ValueError(
            f"kernel_plane={plane!r}: pass 'auto' or one of {KERNEL_PLANES}"
        )
    return plane


def is_pallas(plane: str) -> bool:
    return plane in (PALLAS, PALLAS_INTERPRET)


def plane_interpret(plane: str) -> bool:
    """The ``interpret=`` flag a Pallas plane lowers with."""
    return plane != PALLAS


def describe_plane(plane: str) -> str:
    return {
        JNP: "pure-jnp reference (gather/scatter)",
        PALLAS: "compiled Pallas kernels",
        PALLAS_INTERPRET: "Pallas kernels, interpret mode (CPU CI)",
    }[plane]


# ---------------------------------------------------------------------------
# Engine hot-path dispatch (plane is STATIC: Python branches are free)
# ---------------------------------------------------------------------------


def cas_arbitrate(keys, prio_hi, prio_lo, active, n_records: int, *, plane: str = JNP):
    """Per-key lexicographic-min CAS arbitration over a flat request batch.

    keys/prio_hi/prio_lo (M,) int32, active (M,) bool -> won (M,) bool,
    bitwise-equal across planes (``scatter_min_winner`` semantics)."""
    if not is_pallas(plane):
        return scatter_min_winner(keys, prio_hi, prio_lo, active, n_records)
    won = lock_arbiter(
        keys[None], prio_hi[None], prio_lo[None], active[None],
        interpret=plane_interpret(plane),
    )
    return won[0]


def version_select(wts_hi, wts_lo, ctts_hi, ctts_lo, lock_hi, lock_lo, *, plane: str = JNP):
    """MVCC Cond R1 slot pick + Cond R2 lock check over a flat op batch.

    wts_* (M, S), the rest (M,) int32 -> (found, slot, r2_ok)."""
    if not is_pallas(plane):
        from repro.kernels.ref import mvcc_version_select_ref

        return mvcc_version_select_ref(wts_hi, wts_lo, ctts_hi, ctts_lo, lock_hi, lock_lo)
    return mvcc_version_select(
        wts_hi, wts_lo, ctts_hi, ctts_lo, lock_hi, lock_lo,
        interpret=plane_interpret(plane),
    )


def gather_many(arrs, keys, *, plane: str = JNP):
    """Doorbell-batched multi-array gather: several store arrays at the same
    keys, ONE ``multi_read`` dispatch on a Pallas plane (engine.read_rows_many's
    and planes.node_read_batch's read).  Returns a tuple of per-array
    gathers, each shaped ``keys.shape + arr.shape[1:]``."""
    if not is_pallas(plane):
        kf = keys.reshape(-1)
        return tuple(a[kf].reshape(keys.shape + a.shape[1:]) for a in arrs)
    return multi_read(arrs, keys, interpret=plane_interpret(plane))


# ---------------------------------------------------------------------------
# LM-stack attention wrapper (unchanged contract)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def attention_op(q, k, v, *, causal=True, block_q=128, block_k=128):
    """(B, S, H, Dh) layout in, matching models/lm.py conventions."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention(
        qt, kt, vt, causal=causal, block_q=block_q, block_k=block_k,
        interpret=default_interpret(),
    )
    return out.transpose(0, 2, 1, 3)
