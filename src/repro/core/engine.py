"""Fused, buffer-donated federated round engine over the parameter arena.

BFLN's hot path (paper Fig. 1 steps 3–5) used to be a chain of separate
device programs with host round-trips between them: an eager per-leaf cohort
gather, the jitted train+PAA program, a second jitted fingerprint pipeline,
an eager per-leaf scatter that reallocated the full population params, and a
``global_evaluate`` whose leading dim varied with the arrival count — one
jit recompile per distinct count.

The engine collapses all of it into ONE jitted, ``donate_argnums``-donated
program per (mode, cohort_size):

    arena and data gather → local_train → strategy cohort aggregation
    (BFLN: PAA — prototypes, Pearson, spectral, cluster-masked mean;
    baselines: mask-weighted means / personal models) → cohort fingerprint
    residues → masked scatter-back into the donated arena

The engine is **strategy-generic**: every registered strategy
(`repro.api.registry`) fuses into the same donated step through its
cohort aggregation stages — BFLN keeps its exact PAA op sequence, while the
Table II baselines get fixed-shape mask-weighted aggregation and the
single-cluster CACC view (labels = zeros, affinity = identity).

Arrival is a fixed-shape mask everywhere — no ``np.flatnonzero`` dynamic
indexing, no varying leading dims — so the jit cache hits every round and
the arena buffer is updated in place (donation) instead of reallocating
O(n_clients · N_params) bytes.  The cohort's training data is gathered
inside the step from the population's device-resident data, with the same
index as its arena rows, so the host sends only the cohort ids and arrival
mask (as NumPy; the jit transfers them) and reads back O(cohort) bytes:
fingerprint residues, cluster labels, the Pearson matrix for CACC, and
scalar loss/accuracy.

The async (FedBuff) path has two entries: ``async_step`` trains the
flushed buffer and fingerprints it, and ``async_merge`` is the whole
staleness-weighted merge over flat ``(k, N)`` rows in one program.

Evaluation entries are split so each compiles exactly once: a fixed-shape
mask-weighted cohort eval (round metric), a single-row global eval (async),
and a population eval with its own entry (final metric) so the final pass
never retraces the round-eval program.

Mesh mode (``sharding=`` a client-axis ``NamedSharding`` from
``repro.runtime.arena.ShardedParamArena``): the arena rows stay sharded
across the device mesh — each device holds ``n/shards`` rows and the full
O(n_clients · N_params) matrix never materialises on one device.  The
COHORT axis is sharded end-to-end too (``cohort_mode="sharded"``): the
cohort is padded to a shard multiple (padding slots gather row 0, train on
zero data, and carry zero arrival weight), each device trains its slice and
computes its slice of the batched fingerprints, and aggregation splits into
a shard-local per-slot partial (``Strategy.cohort_partial``; BFLN: client
prototypes) plus a deterministic combine (``Strategy.cohort_combine``) that
runs on the REPLICATED trained cohort block — its cohort-axis reductions
are fixed-order trees / pre-sorted segment sums (``repro.core.aggregation``)
whose replicated program is device-local and matches the single-device
composition bit for bit, and zero-weight padding slots are where-guarded to
contribute exactly +0.0, so seeded replay stays bit-identical to the
single-device engine.  Server payloads that reduce over the cohort
(``Strategy.round_extras`` — the fedprox anchor, fedproto/fedhkd global
prototypes) are computed replicated on the REAL ``[:k]`` slots with the
exact single-device op sequence, then re-padded per client.  The masked
scatter-back writes only the real cohort indices into the rows each device
owns.  ``cohort_mode="replicated"`` keeps the PR 4 behaviour (every device
runs the identical full-shape cohort program) for A/B comparison — it costs
shards× redundant compute.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import staleness_weight, weighted_delta_mean
from repro.core.baselines import barrier_combine_inputs
from repro.core.fl import local_train
from repro.kernels.fingerprint import (
    fingerprint_rows,
    fingerprint_rows_sharded,
    format_digest,
)
from repro.runtime.arena import ArenaLayout, bitcast_u32

Pytree = Any

COHORT_MODES = ("sharded", "replicated")


class SyncRoundOut(NamedTuple):
    """Device outputs of one fused sync round (all O(cohort) or smaller)."""
    labels: jax.Array       # (k,) cluster assignment
    corr: jax.Array         # (k, k) Pearson matrix (CACC input)
    residues: jax.Array     # (k, 2) uint32 fingerprint residues
    mean_loss: jax.Array    # scalar
    new_rows: jax.Array     # (k, N) the cohort's post-scatter arena rows —
                            # eval reads THESE, never the full arena, so the
                            # next round's donation has no pending consumer


def _unfused(x: jax.Array, scalar: jax.Array) -> jax.Array:
    """``x`` unchanged, but behind an integer no-op that XLA cannot fold
    (``scalar != scalar`` is 0 at run time), so an add reading it is not
    contracted with the multiply that made ``x`` into one fused multiply-add
    (CPU codegen does so even across an optimization barrier).  A fused
    multiply-add rounds once where separate ops round twice, which moves
    bits wherever the multiplier is not 1."""
    zero = (scalar != scalar).astype(jnp.int32)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) ^ zero
    return jax.lax.bitcast_convert_type(bits, x.dtype)


class RoundEngine:
    """Jitted entry points for arena-backed federated rounds.

    One instance per simulation; jax caches one executable per entry point
    and cohort size (shapes are otherwise fixed by construction, so varying
    *arrival counts* never retrace).  ``sync_step`` donates the arena —
    callers must rebind, e.g.
    ``arena.data = engine.sync_step(arena.data, ...)[0]``.
    """

    def __init__(
        self,
        layout: ArenaLayout,
        *,
        apply_fn: Callable,
        strategy,                       # repro.core.baselines.Strategy
        opt,                            # repro.optim.Optimizer
        n_clusters: int,
        local_epochs: int,
        stacked_apply_fn: Callable | None = None,
        sharding=None,                  # client-axis NamedSharding (mesh mode)
        cohort_mode: str = "sharded",   # mesh mode: "sharded" | "replicated"
    ):
        if strategy.aggregate_cohort is None:
            raise ValueError(
                f"strategy {strategy.name!r} has no aggregate_cohort stage — "
                "the fused round engine needs the jittable mask-weighted "
                "aggregation (see repro.core.baselines.Strategy)")
        if cohort_mode not in COHORT_MODES:
            raise ValueError(
                f"cohort_mode must be one of {COHORT_MODES}, "
                f"got {cohort_mode!r}")
        self.layout = layout
        self.n_clusters = n_clusters
        self.strategy_name = strategy.name
        self.sharding = sharding
        shards = sharding.mesh.devices.size if sharding is not None else 1
        sharded_cohort = sharding is not None and shards > 1 \
            and cohort_mode == "sharded"
        if sharded_cohort and (strategy.cohort_partial is None
                               or strategy.cohort_combine is None):
            raise ValueError(
                f"strategy {strategy.name!r} has no cohort_partial/"
                "cohort_combine stages — sharded cohort mode needs the "
                "two-stage contract (see repro.core.baselines); use "
                "MeshSpec(cohort='replicated') to fall back to the "
                "replicated cohort program")
        # resolved mode, readable by the driver/bench for obs metadata
        self.cohort_mode = "sharded" if sharded_cohort else (
            "replicated" if sharding is not None else "single")
        self.cohort_shards = shards if sharded_cohort else 1
        pad_mult = self.cohort_shards

        if sharding is not None:
            from repro.launch.sharding import cohort_shardings
            cshard, replicated = cohort_shardings(sharding.mesh)

            def _rep(x):
                """Pin a value replicated: every device holds (and computes)
                the identical full-shape array — the bit-identity anchor for
                the combine stage and all O(k)-sized outputs."""
                return jax.lax.with_sharding_constraint(x, replicated)

            def _shd(x):
                """Pin the population arena to its row sharding."""
                return jax.lax.with_sharding_constraint(x, sharding)

            def _csh(x):
                """Pin a (k_pad, ...) per-slot value to the cohort-axis
                sharding: each device touches only its cohort slice."""
                return jax.lax.with_sharding_constraint(x, cshard)
        else:
            _rep = _shd = _csh = lambda x: x

        def _fingerprint(rows):
            """(m, N) fp32 rows -> (m, 2) residues.  On a mesh the kernel
            runs per device under shard_map — Mosaic kernels cannot be
            partitioned automatically."""
            with jax.named_scope("fingerprint"):
                if sharding is None:
                    return fingerprint_rows(bitcast_u32(rows))
                return fingerprint_rows_sharded(bitcast_u32(rows),
                                                sharding.mesh,
                                                sharding.mesh.axis_names[0])

        def _pad0(x, pad):
            """Append ``pad`` zero slots along the leading (cohort) axis."""
            if pad == 0:
                return x
            return jnp.concatenate(
                [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)

        def _cohort_pad(k: int) -> int:
            return (-(-k // pad_mult) * pad_mult) - k if sharded_cohort else 0

        def _client_accs(params, ex, ey):
            """(m,) per-client accuracy on the shared eval batch.  Uses the
            model's width-concatenated stacked forward when available — the
            vmap form broadcasts the shared batch into a batched dot that
            XLA CPU lowers ~2.5× slower at 100-client cohorts."""
            if stacked_apply_fn is not None:
                logits = stacked_apply_fn(params, ex)          # (m, B, C)
            else:
                logits = jax.vmap(lambda p: apply_fn(p, ex))(params)
            hits = (jnp.argmax(logits, axis=-1) == ey[None, :])
            return jnp.mean(hits.astype(jnp.float32), axis=1)

        def _train(cohort_params, cx, cy, extras):
            opt_state = jax.vmap(opt.init)(cohort_params)
            res = local_train(strategy.local_loss, opt, cohort_params,
                              opt_state, cx, cy, extras, local_epochs,
                              shared_extras=strategy.shared_extras)
            # pin the trained params: every downstream consumer (fingerprint,
            # partial, combine, scatter) must read ONE materialisation — XLA
            # otherwise clones training math into consumer fusions that can
            # vectorise differently, and ULP-divergent clones break replay
            # bit-identity across partitionings (see
            # repro.core.baselines.barrier_combine_inputs)
            return jax.lax.optimization_barrier(res)

        def _pad_extras(extras, pad):
            """Per-client server payloads get zero padding slots; a shared
            payload (no client axis) ships as-is."""
            if strategy.shared_extras or pad == 0:
                return extras
            return jax.tree.map(lambda e: _pad0(e, pad), extras)

        def _sync_step(arena, cohort_idx, data_x, data_y, arrived):
            """``data_x``/``data_y``: the whole population's train data,
            placed like the arena's rows; the cohort's slice is gathered
            here with the rows' own index."""
            k = cohort_idx.shape[0]
            pad = _cohort_pad(k)
            if sharded_cohort:
                with jax.named_scope("gather"):
                    # padding slots gather row 0 (any valid row — their
                    # outputs are sliced away and their arrival weight is
                    # zero) and train on zero data
                    idx_p = jnp.concatenate(
                        [cohort_idx, jnp.zeros((pad,), cohort_idx.dtype)]) \
                        if pad else cohort_idx
                    # shard-aware gather: each device receives only its
                    # cohort slice — no replicated (k, N) block materialises
                    rows = _csh(arena[idx_p])
                    cx, cy = data_x[cohort_idx], data_y[cohort_idx]
                    cx_p, cy_p = _csh(_pad0(cx, pad)), _csh(_pad0(cy, pad))
                    arrived_p = _pad0(arrived, pad)
                # server payload on the replicated REAL slots with the exact
                # single-device op sequence: round_extras may reduce over
                # the cohort (fedprox anchor, fedproto/fedhkd global
                # prototypes) and must never see padding slots.  Inputs AND
                # outputs are pinned replicated — leaving the output free
                # lets GSPMD back-propagate the training consumer's cohort
                # sharding through the broadcast into the reduction,
                # rewriting it into partial sums + all-reduce (ULP flips)
                with jax.named_scope("local_train"):
                    rows_real = _rep(rows[:k])
                    extras = _pad_extras(jax.tree.map(
                        _rep, strategy.round_extras(
                            layout.unflatten(rows_real), _rep(cx),
                            _rep(cy))), pad)
                    res = _train(layout.unflatten(rows), cx_p, cy_p, extras)
                # shard-local per-slot partial (BFLN: prototypes); only this
                # small matrix replicates into the deterministic combine —
                # whose cohort-axis reductions are fixed-order trees, so the
                # bits match the single-device composition exactly
                partial = strategy.cohort_partial(res.params, cx_p, cy_p,
                                                  arrived_p)
                if partial is not None:
                    partial = jax.tree.map(_rep, partial)
                # the combine runs fully REPLICATED: left cohort-sharded,
                # GSPMD rewrites the fixed-order tree levels into pair
                # all-reduces whose rounding path diverges from the
                # single-device composition by 1 ULP at near-halfway cases.
                # Replicating first keeps every combine op device-local and
                # bit-identical to mesh_shards=1; only the small (k_pad, N)
                # cohort block replicates, never the (n, N) arena.
                sp_rep = jax.tree.map(_rep, res.params)
                sp_b, partial_b = barrier_combine_inputs(sp_rep, partial)
                # named scope -> HLO metadata op_name: lets the compiled-
                # artifact audit (repro.analysis.hlo_audit) attribute any
                # collective inside the combine phase and fail the build —
                # an all-reduce here IS the partial-sum drift bug.  The
                # OUTPUTS are pinned replicated as well: constraining only
                # the inputs leaves GSPMD free to propagate the row-sharded
                # scatter layout backwards and partition the combine body
                # (kmeans/eigh dots pick up partial-sum all-reduces); with
                # both ends pinned the body compiles device-local and any
                # resharding happens after the scope, on the small outputs
                with jax.named_scope("cohort_combine"):
                    # arrived_p also feeds the cohort-SHARDED partial stage;
                    # the combine gets its own replicated pin, or GSPMD
                    # propagates the sharding through the arrival weighting
                    # into the clustering interior
                    agg = strategy.cohort_combine(sp_b, partial_b,
                                                  _rep(arrived_p), k)
                    agg = jax.tree.map(_rep, agg)
                local_rows = layout.flatten(res.params)    # (k_pad, N) sharded
                residues = _fingerprint(local_rows)[:k]
                mean_loss = jnp.mean(res.mean_loss[:k])
                prev_rows = rows[:k]
            else:
                with jax.named_scope("gather"):
                    rows = _rep(arena[cohort_idx])
                    cx = _rep(data_x[cohort_idx])
                    cy = _rep(data_y[cohort_idx])
                with jax.named_scope("local_train"):
                    extras = strategy.round_extras(layout.unflatten(rows),
                                                   cx, cy)
                    res = _train(layout.unflatten(rows), cx, cy, extras)
                # aggregation over ALL cohort slots (stragglers burn local
                # compute too); only the aggregation weights honour the
                # arrival mask
                with jax.named_scope("cohort_combine"):
                    agg = strategy.aggregate_cohort(res.params, cx, cy,
                                                    arrived)
                local_rows = layout.flatten(res.params)
                # pinned replicated: on a mesh the kernel's per-device row
                # split would otherwise propagate back into training
                residues = _fingerprint(_rep(local_rows))
                mean_loss = jnp.mean(res.mean_loss)
                prev_rows = rows
            # masked scatter-back: arrived slots adopt their aggregated
            # params, everyone else keeps their previous personalized row.
            # Only the k REAL indices are written (a padded scatter would
            # race its duplicate row-0 slots), and each device lands only
            # the rows it owns — the donated arena stays row-sharded.
            with jax.named_scope("scatter_back"):
                new_rows = layout.flatten(agg.stacked_params)
                upd = jnp.where(arrived[:, None] > 0, new_rows, prev_rows)
                arena = _shd(arena.at[cohort_idx].set(upd))
            return arena, SyncRoundOut(agg.labels, agg.corr, residues,
                                       mean_loss, upd)

        def _async_step(base_rows, cx, cy):
            """FedBuff flush batch: local updates + digests, no aggregation.
            ``base_rows`` is the (k, N) rows each buffered client trained
            from, or those k (N,) rows as a sequence (the driver passes its
            version snapshots), stacked here.  The merge is gated by chain
            verification (a host decision) and runs afterwards in
            ``async_merge``."""
            base_rows = jnp.asarray(base_rows)
            k = base_rows.shape[0]
            pad = _cohort_pad(k)
            if sharded_cohort:
                with jax.named_scope("gather"):
                    rows = _csh(_pad0(base_rows, pad))
                    cx_p, cy_p = _csh(_pad0(cx, pad)), _csh(_pad0(cy, pad))
                # extras replicated end-to-end, as in the sync step: the
                # flush-batch rows feed both the sharded training gather and
                # the cohort-reducing server payload, and the latter must
                # keep the single-device op sequence
                with jax.named_scope("local_train"):
                    extras = _pad_extras(jax.tree.map(
                        _rep, strategy.round_extras(
                            layout.unflatten(_rep(base_rows)), _rep(cx),
                            _rep(cy))), pad)
                    res = _train(layout.unflatten(rows), cx_p, cy_p, extras)
                local_rows_p = layout.flatten(res.params)
                residues = _fingerprint(local_rows_p)[:k]
                local_rows = _rep(local_rows_p[:k])
                mean_loss = jnp.mean(res.mean_loss[:k])
                return local_rows, residues, mean_loss
            with jax.named_scope("local_train"):
                extras = strategy.round_extras(layout.unflatten(base_rows),
                                               cx, cy)
                res = _train(layout.unflatten(base_rows), cx, cy, extras)
            local_rows = layout.flatten(res.params)
            residues = _fingerprint(_rep(local_rows))
            return local_rows, residues, jnp.mean(res.mean_loss)

        def _async_merge(global_row, local_rows, base_rows, staleness,
                         verified, alpha, server_lr):
            """The whole FedBuff merge in one program over flat (k, N) rows:
            deltas, the (1 + s)^-alpha weights gated by the chain's verdicts,
            their fixed-order weighted mean (``weighted_delta_mean`` on one
            (k, N) leaf — elementwise the same tree as per leaf, so the bits
            equal the legacy driver's per-leaf merge) and the server update.
            Runs replicated, so replay is bit-identical across mesh widths.
            Returns the new global row and the applied weights."""
            delta = _rep(jnp.asarray(local_rows)) \
                - _rep(jnp.asarray(base_rows))
            w = staleness_weight(staleness, alpha) \
                * jnp.asarray(verified, jnp.float32)
            merged = weighted_delta_mean(delta, w)
            step = _unfused(server_lr * merged, server_lr)
            return _rep(global_row) + step, w

        def _eval_cohort(cohort_rows, arrived, labels, ex, ey):
            """Fixed-shape mask-weighted cohort accuracy (the jnp-generic
            reference is ``repro.core.fl.masked_global_evaluate``).  Takes
            the cohort's (k, N) rows — NOT the arena — so a deferred eval
            never blocks the next round's arena donation.  In sharded mode
            the per-client forwards shard over the cohort axis; the scalar
            combine runs on the replicated (k,) accuracies with the exact
            single-device op sequence."""
            k = cohort_rows.shape[0]
            pad = _cohort_pad(k)
            if sharded_cohort:
                rows = _csh(_pad0(cohort_rows, pad))
                accs = _rep(_client_accs(layout.unflatten(rows), ex, ey)[:k])
            else:
                accs = _client_accs(layout.unflatten(cohort_rows), ex, ey)
            w = arrived.astype(jnp.float32)
            acc = jnp.sum(accs * w) / jnp.maximum(jnp.sum(w), 1.0)
            onehot = jax.nn.one_hot(labels, n_clusters, dtype=jnp.float32) \
                * w[:, None]
            sizes = jnp.sum(onehot, axis=0)                   # (C,) arrived
            cacc = jnp.sum(onehot * accs[:, None], axis=0) \
                / jnp.maximum(sizes, 1.0)
            return acc, cacc

        def _eval_global(global_row, ex, ey):
            return _client_accs(layout.unflatten(global_row[None]), ex, ey)[0]

        def _eval_population(arena, ids, ex, ey):
            n = ids.shape[0]
            pad = _cohort_pad(n)
            if sharded_cohort:
                # duplicate id 0 into the padding slots; their accuracies
                # are sliced away before the mean
                ids_p = jnp.concatenate(
                    [ids, jnp.zeros((pad,), ids.dtype)]) if pad else ids
                rows = _csh(arena[ids_p])
                accs = _rep(_client_accs(layout.unflatten(rows), ex, ey)[:n])
                return jnp.mean(accs)
            rows = _rep(arena[ids])       # replicate only the sampled rows
            return jnp.mean(_client_accs(layout.unflatten(rows), ex, ey))

        self.sync_step = jax.jit(_sync_step, donate_argnums=(0,))
        self.async_step = jax.jit(_async_step)
        # the global row it reads is also a live version snapshot: no donation
        self.async_merge = jax.jit(_async_merge, donate_argnums=())
        self.eval_cohort = jax.jit(_eval_cohort)
        self.eval_global = jax.jit(_eval_global)
        self.eval_population = jax.jit(_eval_population)
        # the jitted entries by name, for cache_sizes() and lower_entry()
        self._entries = {
            "sync_step": self.sync_step,
            "async_step": self.async_step,
            "async_merge": self.async_merge,
            "eval_cohort": self.eval_cohort,
            "eval_global": self.eval_global,
            "eval_population": self.eval_population,
        }

    # ------------------------------------------------------------------ #

    def cache_sizes(self) -> dict[str, int]:
        """Compiled-executable count per entry point (jit cache sizes).

        The engine's contract is ONE compile per entry per (mode,
        cohort_size) — arrival-count variation must never retrace.  The
        round benchmark and the cache-stability regression test assert on
        this dict.
        """
        return {name: fn._cache_size() for name, fn in self._entries.items()}

    def entry_names(self) -> list[str]:
        """The engine's jitted entry points, in a fixed order."""
        return list(self._entries)

    def lower_entry(self, name: str, *args):
        """Lower (without executing) the jitted entry ``name`` on ``args`` —
        the hook the compiled-artifact audit uses to inspect the exact
        programs the driver runs."""
        return self._entries[name].lower(*args)

    def format_digests(self, residues) -> list[str]:
        """(k, 2) uint32 residues -> per-client digest strings (host side)."""
        res = np.asarray(jax.device_get(residues))
        return [format_digest(row, self.layout.n_params) for row in res]
