"""Size bounds of the Pallas SpGEMM kernels, derived from v5e compiles.

Each kernel keeps a whole operand in one on-chip memory by design, so each
has a largest size the TPU compiler accepts. The numbers below are what
Mosaic accepted when compiling for a described v5e (``v5e:2x2`` topology,
one chip: 1 MiB SMEM, 128 MiB VMEM); ``tests/test_tpu_compile.py`` compiles
every kernel at its bound and checks that one step past it is refused here.

  * ELL kernels (``spgemm_symbolic``, ``spgemm_numeric`` = dense_acc,
    ``spgemm_lp`` = flat_lp) scalar-prefetch A's whole ELL structure plus
    per-row counts into SMEM: at most ``SMEM_WORDS`` int32 words in all
    (1 MiB less what the compiler reserves). Their per-row VMEM tiles are
    bounded per kernel below.
  * Replay kernels (``segsum_reuse``, ``lp_reuse``) map both value buffers
    and the whole output as single VMEM blocks.

Above a bound the entry point raises ``SpgemmConfigError`` before anything
reaches the compiler, and kernel selection ("auto", ``tune="measure"``)
never picks the kernel. Rebuilding these kernels to stream their operands
is what lifts the bounds.
"""
from __future__ import annotations

SMEM_WORDS = 258_048  # int32 scalar-prefetch words (1 MiB SMEM less reserve)
# dense_acc: the (1, k_pad) accumulator and the (rB|rC, 512) one-hot tiles
DENSE_ACC_MAX_K_PAD = 1 << 21
DENSE_ACC_MAX_WIDTH = 4096  # rB and rC each (8192 overflows with f32 passes)
# flat_lp: the emit step compares every L1/L2 slot with every C column
FLAT_LP_MAX_TABLE_BY_RC = (4096 + 4096) * 2048  # (l1 + l2) * rC
SYMBOLIC_MAX_K32 = 1 << 20  # (1, k32) bitmask row and accumulator
# replay kernels: A and B value buffers, and the (1, nnz_cap + window) output
REPLAY_MAX_VALUES = 3 << 20  # na + nb, padded
REPLAY_MAX_NNZ = 1 << 24


def _pow2(x: int) -> int:
    return 1 << (max(int(x), 1) - 1).bit_length()


def ell_misfit(kernel: str, *, m: int, r_a: int, n: int = 0, r_b: int = 0,
               r_c: int = 0, k: int = 0, l1_size: int | None = None
               ) -> str | None:
    """Why ELL kernel ``kernel`` ("symbolic", "dense_acc" or "flat_lp")
    cannot take these padded sizes, or None.

    m, r_a: A's rows and ELL width; n, r_b: B's rows and ELL width; r_c: C's
    ELL width; k: B's columns; l1_size: flat_lp's L1 table (default 2*rC
    rounded up to a power of two, as the kernel sizes it).
    """
    # A's ELL columns plus the per-row counts each kernel prefetches
    words = m * r_a + {"symbolic": m, "dense_acc": 2 * m,
                       "flat_lp": 2 * m + n}[kernel]
    if words > SMEM_WORDS:
        return (f"{kernel}: {words} scalar-prefetch words exceed the "
                f"{SMEM_WORDS}-word SMEM bound")
    if kernel == "symbolic":
        k32 = -(-k // 32)
        if k32 > SYMBOLIC_MAX_K32:
            return (f"symbolic: k32={k32} exceeds the VMEM bound "
                    f"{SYMBOLIC_MAX_K32}")
    elif kernel == "dense_acc":
        k_pad = -(-k // 512) * 512
        if k_pad > DENSE_ACC_MAX_K_PAD or max(r_b, r_c) > DENSE_ACC_MAX_WIDTH:
            return (f"dense_acc: k_pad={k_pad}, rB={r_b}, rC={r_c} exceed "
                    f"the VMEM bounds k_pad<={DENSE_ACC_MAX_K_PAD}, "
                    f"rB,rC<={DENSE_ACC_MAX_WIDTH}")
    else:
        s2 = _pow2(max(2 * r_c, 8))
        table = (s2 if l1_size is None else l1_size) + s2
        if table * r_c > FLAT_LP_MAX_TABLE_BY_RC:
            return (f"flat_lp: (l1+l2)*rC={table * r_c} exceeds the VMEM "
                    f"bound {FLAT_LP_MAX_TABLE_BY_RC}")
    return None


def replay_misfit(na: int, nb: int, nnz_cap: int) -> str | None:
    """Why a replay kernel cannot hold these padded value buffers and
    output, or None."""
    if na + nb > REPLAY_MAX_VALUES or nnz_cap > REPLAY_MAX_NNZ:
        return (f"replay kernel: values {na}+{nb}, nnz_cap={nnz_cap} exceed "
                f"the VMEM bounds values<={REPLAY_MAX_VALUES}, "
                f"nnz_cap<={REPLAY_MAX_NNZ}")
    return None


def require_fit(reason: str | None) -> None:
    """Raise the typed config error for a misfit reason."""
    if reason is not None:
        from repro.runtime.validate import SpgemmConfigError  # cycle-free

        raise SpgemmConfigError(reason)
