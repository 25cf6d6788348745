"""ShardedReuseExecutor: pinned sharded plans replayed as ONE mesh dispatch.

The single-device ``ReuseExecutor`` (core/executor.py) made the paper's
Reuse case cheap to *dispatch*; this is the same contract lifted onto a
mesh. Construction pins a ``ShardedPlan`` (one ``structure_key`` hash, ever
— probed against the mesh-aware plan cache so repeated structures never
re-shard or re-trace) and every ``apply`` is a single jitted dispatch of a
``jax.shard_map``: per shard, two gathers + one sorted segment-sum — the
identical ``numeric_reuse`` replay, just running S-wide.

Value routing is part of the plan, so replays never touch structure:

  * fresh A values enter *global* ``(a_nnz_cap,)`` and are re-sharded by the
    pinned ``a_perm`` gather inside the dispatch;
  * replicated B: values pass through unsharded (zero communication — the
    paper's memory-for-communication trade);
  * allgather B: values are sharded by ``b_shard_perm``, all-gathered inside
    the dispatch, and routed into the concatenated layout by ``b_perm``. The
    *structure* all-gather was hoisted to plan-build time — the per-replay
    collective moves only ``(S, b_cap)`` values, not the CSR triplet.

``apply_batched`` maps the per-shard replay over stacked value arrays
``(batch, nnz_cap)`` — one dispatch for the whole batch across the whole
mesh. Replays are bitwise identical to the single-device executor after
``merge_shards``: each shard's products are the same products in the same
sorted order as the corresponding slice of the global plan.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core.distributed import ShardedCSR, merge_shards
from repro.core.executor import DISPATCH_COUNTS, map_batch
from repro.core.meta import DEFAULT_PAD_POLICY
from repro.core.plan_cache import structure_key
from repro.core.spgemm import (
    SpgemmPlan,
    _note_trace,
    numeric_reuse,
    prepare_sparse_inputs,
)
from repro.dist.plan import B_PLACEMENTS, ShardedPlan, build_sharded_plan
from repro.dist.plan_cache import default_dist_plan_cache, dist_plan_key
from repro.obs import trace as obs_trace
from repro.runtime.validate import (PlanMismatchError, SpgemmConfigError,
                                    SpgemmInputError,
                                    check_csr, resolve_mode)
from repro.sparse.formats import CSR


def _local_plan(ip, ix, seg, asl, bsl, m_loc: int, k: int) -> SpgemmPlan:
    """Strip the leading per-device shard axis -> this shard's SpgemmPlan."""
    return SpgemmPlan(indptr=ip[0], indices=ix[0], seg_ids=seg[0],
                      a_slot_s=asl[0], b_slot_s=bsl[0], shape=(m_loc, k))


@partial(jax.jit, static_argnames=("mesh", "axis", "m_loc", "k", "a_axis", "b_axis"))
def _replay_replicated(ip, ix, seg, asl, bsl, aperm, a_values, b_values,
                       *, mesh, axis, m_loc, k, a_axis, b_axis):
    """One dispatch: per-shard numeric replay with B replicated.

    ``a_axis``/``b_axis`` of ``None`` mean unbatched operands (plain
    ``apply``); 0 means a leading batch axis (``apply_batched``).
    """
    _note_trace("dist_replay")
    batched = a_axis is not None or b_axis is not None

    def fn(ip, ix, seg, asl, bsl, aperm, a_values, b_values):
        plan = _local_plan(ip, ix, seg, asl, bsl, m_loc, k)
        ap = aperm[0]
        if not batched:
            return numeric_reuse(plan, a_values[ap], b_values)[None]
        out = map_batch(lambda av, bv: numeric_reuse(plan, av[ap], bv),
                        a_values, b_values, a_axis, b_axis)
        return out[None]  # (1, batch, nnz_cap)

    out = shard_map(
        fn, mesh=mesh,
        in_specs=(P(axis),) * 6 + (P(), P()),
        out_specs=P(axis),
    )(ip, ix, seg, asl, bsl, aperm, a_values, b_values)
    return jnp.swapaxes(out, 0, 1) if batched else out


@partial(jax.jit, static_argnames=("mesh", "axis", "m_loc", "k", "a_axis", "b_axis"))
def _replay_allgather(ip, ix, seg, asl, bsl, aperm, bshard, bperm,
                      a_values, b_values, *, mesh, axis, m_loc, k,
                      a_axis, b_axis):
    """One dispatch: shard B values, all-gather them inside the mesh, route
    into the pinned concat layout, replay. Structure never moves."""
    _note_trace("dist_replay")
    batched = a_axis is not None or b_axis is not None
    # shard B values by the pinned map: (S, b_cap) or (batch, S, b_cap)
    b_sh = b_values[..., bshard] if b_axis == 0 else b_values[bshard]
    if b_axis == 0:
        b_sh = jnp.moveaxis(b_sh, 0, 1)  # (S, batch, b_cap): shard axis leads

    def fn(ip, ix, seg, asl, bsl, aperm, bperm, a_values, b_sh):
        plan = _local_plan(ip, ix, seg, asl, bsl, m_loc, k)
        ap = aperm[0]
        gathered = jax.lax.all_gather(b_sh[0], axis)  # (S, [batch,] b_cap)
        if b_axis == 0:
            flat = jnp.moveaxis(gathered, 0, 1).reshape(gathered.shape[1], -1)
            bg = flat[:, bperm]  # (batch, S*b_cap) in concat layout
        else:
            bg = gathered.reshape(-1)[bperm]
        if not batched:
            return numeric_reuse(plan, a_values[ap], bg)[None]
        out = map_batch(lambda av, bv: numeric_reuse(plan, av[ap], bv),
                        a_values, bg, a_axis, b_axis)
        return out[None]

    out = shard_map(
        fn, mesh=mesh,
        in_specs=(P(axis),) * 6 + (P(), P(), P(axis)),
        out_specs=P(axis),
    )(ip, ix, seg, asl, bsl, aperm, bperm, a_values, b_sh)
    return jnp.swapaxes(out, 0, 1) if batched else out


class ShardedReuseExecutor:
    """A pinned ``ShardedPlan`` exposed as a mesh replay engine.

    Construction is the only host-side work (partitioning, one structure
    hash, one sharded symbolic pass on a cache miss); from then on every
    ``apply`` / ``apply_batched`` is one jitted ``shard_map`` dispatch —
    zero hashing, zero cache probes, zero retraces for fixed value shapes.
    """

    def __init__(self, plan: ShardedPlan, mesh, *, axis: str = "data",
                 b_placement: str = "replicated",
                 validate: str | None = "off"):
        if b_placement not in B_PLACEMENTS:
            raise SpgemmConfigError(
                f"unknown b_placement {b_placement!r}; expected one of "
                f"{B_PLACEMENTS}")
        if mesh.shape[axis] != plan.num_shards:
            raise PlanMismatchError(
                f"plan has {plan.num_shards} shards but mesh axis "
                f"{axis!r} has {mesh.shape[axis]} devices")
        self.plan = plan
        self.mesh = mesh
        self.axis = axis
        self.b_placement = b_placement
        self.cache_state = "pinned"
        self._merge_perm = None  # built lazily by merge_values
        # validate= mirrors ReuseExecutor: a literal "off" default (the
        # replay hot path must not silently change under $REPRO_VALIDATE);
        # pin-time syncs of two scalars buy O(1) per-replay operand checks
        self.validate_mode = resolve_mode(validate)
        self._a_req = self._b_req = 0
        if self.validate_mode != "off":
            # operand requirements over LIVE products only (padding slots
            # are clamped to build-time caps and dropped by sentinel
            # seg_ids — see runtime.validate.PlanGuard): trace each live
            # product's slot back through the pinned routing perms to the
            # global value slot it actually reads
            seg = np.asarray(plan.seg_ids)  # (S, fm_cap)
            live = seg < plan.nnz_cap
            asl = np.asarray(plan.a_slot_s)
            bsl = np.asarray(plan.b_slot_s)
            aperm = np.asarray(plan.a_perm)  # (S, a_cap): local -> global
            ga = np.take_along_axis(
                aperm, np.minimum(asl, aperm.shape[1] - 1), axis=1)
            self._a_req = int(ga[live].max()) + 1 if live.any() else 0
            if b_placement == "replicated":
                # replicated replay gathers global B values via b_slot_s
                gb = bsl[live]
            else:
                # concat slot -> gathered flat slot -> global value slot
                bperm = np.asarray(plan.b_perm)
                flatshard = np.asarray(plan.b_shard_perm).reshape(-1)
                gb = flatshard[bperm[np.minimum(bsl[live],
                                                len(bperm) - 1)]]
            self._b_req = int(gb.max()) + 1 if gb.size else 0

    def _check_values(self, a_values, b_values, batched: bool) -> None:
        """Per-replay operand check (validate != "off"): global value-buffer
        lengths against the pinned routing perms (``PlanMismatchError``),
        plus a device finiteness sweep in "device" mode."""
        for side, vals, req in (("A", a_values, self._a_req),
                                ("B", b_values, self._b_req)):
            ok_ndim = vals.ndim in (1, 2) if batched else vals.ndim == 1
            if not ok_ndim:
                raise PlanMismatchError(
                    f"{side} values must be "
                    f"{'(batch, nnz) or (nnz,)' if batched else '1-D (nnz,)'}"
                    f" in the flat global layout, got shape "
                    f"{tuple(vals.shape)}")
            if vals.shape[-1] < req:
                raise PlanMismatchError(
                    f"{side} value buffer has {vals.shape[-1]} slots but the "
                    f"pinned sharded plan routes up to slot {req - 1} — "
                    f"replaying against operands from a different structure?")
            if (self.validate_mode == "device"
                    and jnp.issubdtype(vals.dtype, jnp.floating)
                    and not bool(jnp.all(jnp.isfinite(vals)))):
                raise SpgemmInputError(
                    f"{side} values contain NaN/Inf (device validation)")

    @classmethod
    def from_matrices(cls, a: CSR, b: CSR, mesh, *, axis: str = "data",
                      b_placement: str = "replicated",
                      pad_policy: str | None = None,
                      plan_cache=None, validate: str | None = "off",
                      _prepared=None) -> "ShardedReuseExecutor":
        """Build (or fetch from the mesh-aware plan cache) the sharded plan
        for ``a @ b`` and pin it. One structure hash, ever; a cache hit
        skips partitioning, the sharded symbolic pass, and the plan build —
        repeated structures never re-shard.

        ``_prepared``: a caller that already ran ``prepare_sparse_inputs``
        (sharded_spgemm) passes its tuple here to skip the second host-sync
        preamble; the executor keeps no reference to the operands either
        way — replays take fresh values as arguments.
        """
        policy = DEFAULT_PAD_POLICY if pad_policy is None else pad_policy
        vmode = resolve_mode(validate)
        if vmode != "off":
            check_csr(a, vmode, name="A")
            check_csr(b, vmode, name="B")
        if _prepared is None:
            _prepared = prepare_sparse_inputs(a, b, policy)
        a, b, _, _, fm_cap = _prepared
        skey = structure_key(a, b, fm_cap, policy)  # the one hash
        if plan_cache is None:
            cache = default_dist_plan_cache()
        elif plan_cache is False:
            cache = None
        else:
            cache = plan_cache
        key = dist_plan_key(skey, mesh.shape[axis], b_placement)
        plan = cache.get(key) if cache is not None else None
        state = "hit"
        if plan is None:
            plan = build_sharded_plan(a, b, mesh, axis=axis,
                                      b_placement=b_placement,
                                      pad_policy=policy)
            if cache is not None:
                cache.put(key, plan)
                state = "miss"
            else:
                state = "bypass"
        ex = cls(plan, mesh, axis=axis, b_placement=b_placement,
                 validate=vmode)
        ex.cache_state = state
        return ex

    @property
    def shape(self) -> tuple:
        return tuple(self.plan.shape)

    @property
    def num_shards(self) -> int:
        return self.plan.num_shards

    @property
    def nnz_cap(self) -> int:
        return self.plan.nnz_cap

    def _replay(self, a_values, b_values, a_axis, b_axis):
        p = self.plan
        kwargs = dict(mesh=self.mesh, axis=self.axis, m_loc=p.m_loc,
                      k=self.shape[1], a_axis=a_axis, b_axis=b_axis)
        if self.b_placement == "replicated":
            return _replay_replicated(p.indptr, p.indices, p.seg_ids,
                                      p.a_slot_s, p.b_slot_s, p.a_perm,
                                      a_values, b_values, **kwargs)
        return _replay_allgather(p.indptr, p.indices, p.seg_ids,
                                 p.a_slot_s, p.b_slot_s, p.a_perm,
                                 p.b_shard_perm, p.b_perm,
                                 a_values, b_values, **kwargs)

    def apply(self, a_values: jax.Array, b_values: jax.Array) -> jax.Array:
        """Replay on new *global* operand values -> (S, nnz_cap) C values.

        Operand values use the same flat global layout as the single-device
        executor (the pinned perms re-shard them inside the dispatch), so a
        serving loop can switch meshes without reshaping its buffers.
        """
        DISPATCH_COUNTS["dist_apply"] += 1
        if self.validate_mode != "off":
            self._check_values(a_values, b_values, batched=False)
        with obs_trace.span("dist.replay", placement=self.b_placement,
                            shards=self.num_shards):
            return self._replay(a_values, b_values, None, None)

    def apply_batched(self, a_values: jax.Array,
                      b_values: jax.Array) -> jax.Array:
        """Replay stacked values in ONE dispatch -> (batch, S, nnz_cap).

        Either operand may be stacked ``(batch, operand_nnz_cap)`` or shared
        unbatched ``(operand_nnz_cap,)``; at least one must be stacked.
        """
        DISPATCH_COUNTS["dist_apply_batched"] += 1
        a_axis = 0 if a_values.ndim == 2 else None
        b_axis = 0 if b_values.ndim == 2 else None
        if a_axis is None and b_axis is None:
            raise SpgemmConfigError(
                "apply_batched needs at least one stacked (batch, nnz) "
                "operand; use apply() for a single replay")
        if self.validate_mode != "off":
            self._check_values(a_values, b_values, batched=True)
        with obs_trace.span("dist.replay", placement=self.b_placement,
                            shards=self.num_shards,
                            batch=(a_values.shape[0] if a_axis == 0
                                   else b_values.shape[0])):
            return self._replay(a_values, b_values, a_axis, b_axis)

    def to_sharded_csr(self, values: jax.Array) -> ShardedCSR:
        """Wrap one replay's (S, nnz_cap) values in the plan's C structure."""
        want = (self.num_shards, self.nnz_cap)
        if tuple(values.shape) != want:
            raise PlanMismatchError(
                f"expected ONE replay's (S, nnz_cap)={want} values, got "
                f"{tuple(values.shape)}; apply_batched output carries a "
                f"leading batch axis — index a batch element first")
        return ShardedCSR(indptr=self.plan.indptr, indices=self.plan.indices,
                          values=values, shape=self.shape)

    def merge(self, values: jax.Array) -> CSR:
        """Host-side: merge one replay's (S, nnz_cap) values into global C."""
        return merge_shards(self.to_sharded_csr(values), self.shape[0])

    def merge_values(self, values: jax.Array) -> jax.Array:
        """Device-side merge: one replay's (S, nnz_cap) values -> the flat
        global value layout of ``merge(...)`` (live slots, row-major).

        One jittable gather through a perm pinned on first use — the
        serving-loop alternative to ``merge`` when only *values* must reach
        the global layout (e.g. feeding the next pinned multiply of a
        V-cycle): no host transfer, no per-shard numpy concat.
        """
        want = (self.num_shards, self.nnz_cap)
        if tuple(values.shape) != want:
            raise PlanMismatchError(
                f"merge_values takes one replay's (S, nnz_cap)={want} "
                f"values, got {tuple(values.shape)}; index a batch element "
                f"of apply_batched output first")
        if self._merge_perm is None:
            ip = np.asarray(self.plan.indptr)
            m, m_loc = self.shape[0], self.plan.m_loc
            perm = []
            for s in range(self.num_shards):
                rows = min(m_loc, max(m - s * m_loc, 0))
                nnz_s = int(ip[s, rows]) if rows else 0
                perm.append(s * self.nnz_cap + np.arange(nnz_s, dtype=np.int64))
            self._merge_perm = jnp.asarray(
                np.concatenate(perm) if perm else np.zeros(0, np.int64),
                jnp.int32)
        return values.reshape(-1)[self._merge_perm]


def sharded_spgemm(a: CSR, b: CSR, mesh, *, axis: str = "data",
                   b_placement: str = "replicated",
                   pad_policy: str | None = None, plan_cache=None):
    """One sharded multiply through the pinned-plan machinery.

    The mesh entry point behind ``spgemm(..., mesh=...)``: resolves (or
    builds) the sharded plan via the mesh-aware cache, replays once, merges.
    Returns a ``SpgemmResult`` whose ``plan`` is the ``ShardedPlan`` — hand
    it to ``ShardedReuseExecutor`` to keep replaying without re-hashing.
    """
    from repro.core.spgemm import SpgemmResult

    policy = DEFAULT_PAD_POLICY if pad_policy is None else pad_policy
    prepared = prepare_sparse_inputs(a, b, policy)
    a, b, fm, maxrf, fm_cap = prepared
    ex = ShardedReuseExecutor.from_matrices(
        a, b, mesh, axis=axis, b_placement=b_placement, pad_policy=policy,
        plan_cache=plan_cache, _prepared=prepared)
    values = ex.apply(a.values, b.values)
    c = ex.merge(values)
    stats = {
        "method": "sparse",
        "pad_policy": policy,
        "fm": fm,
        "maxrf": maxrf,
        "fm_cap": fm_cap,
        "cache": ex.cache_state,
        "mesh_shape": tuple(mesh.devices.shape),
        "mesh_axis": axis,
        "num_shards": ex.num_shards,
        "b_placement": b_placement,
        "nnz_c": int(c.indptr[-1]),
        "nnz_cap": ex.nnz_cap,
    }
    return SpgemmResult(c=c, plan=ex.plan, stats=stats)
