"""Parameter arena: ONE canonical flat layout for population-stacked params.

Before this module, three subsystems each invented their own flattening of
the stacked parameter pytree: ``kernels.fingerprint`` re-stacked-and-raveled
every leaf per digest call, ``kernels.cluster_agg`` asked its callers to
hand-build an ``(m, N)`` matrix, and the sim driver shuttled whole pytrees
through per-leaf host-side gathers and scatters — an O(n_clients · N_params)
reallocation every round.  The arena flattens the population ONCE into a
single ``(n_clients, N_params)`` matrix with a recorded leaf layout, and
everything downstream (cohort gather, cluster-masked FedAvg, fingerprint
digests, masked scatter-back) operates on rows of that matrix.

Canonical leaf order is **path-sorted** (``jax.tree_util.keystr``), the same
order ``kernels.fingerprint`` has always used — so digests of arena rows are
bit-identical to digests of the original pytrees.  Flatten/unflatten are
pure reshape/concat (no arithmetic); the value path accepts only leaf
dtypes exactly representable in the arena dtype (fp32 arena: f32/bf16/f16),
so round-tripping is exact and the views fuse away inside a jitted
program.  The uint32 *bit* view for fingerprinting (``flatten_u32``) is
separate and keeps the legacy permissive cast semantics.

The :class:`ParamArena` wrapper is a host-side convenience; the fused round
engine (``repro.core.engine``) passes the raw ``data`` matrix through its
donated jitted step and writes the result back.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


@dataclass(frozen=True)
class ArenaLayout:
    """Recorded flat layout of a stacked pytree (leading client axis).

    ``paths``/``shapes``/``dtypes``/``sizes``/``offsets`` describe the leaves
    in canonical (path-sorted) column order; ``treedef`` plus ``order`` (the
    permutation from tree order to canonical order) reconstruct the pytree.
    """

    treedef: Any = field(repr=False)
    paths: tuple[str, ...]
    shapes: tuple[tuple[int, ...], ...]   # per-client shapes (no client axis)
    dtypes: tuple[Any, ...]
    sizes: tuple[int, ...]
    offsets: tuple[int, ...]
    order: tuple[int, ...]                # canonical position -> tree position
    dtype: Any = jnp.float32              # arena storage dtype

    @property
    def n_params(self) -> int:
        return int(sum(self.sizes))

    # ------------------------------------------------------------------ #

    @classmethod
    def from_stacked(cls, stacked: Pytree, dtype=jnp.float32) -> "ArenaLayout":
        leaves_p, treedef = jax.tree_util.tree_flatten_with_path(stacked)
        keystrs = [jax.tree_util.keystr(p) for p, _ in leaves_p]
        order = tuple(sorted(range(len(leaves_p)), key=lambda i: keystrs[i]))
        paths, shapes, dtypes, sizes = [], [], [], []
        for i in order:
            leaf = leaves_p[i][1]
            paths.append(keystrs[i])
            shapes.append(tuple(leaf.shape[1:]))
            dtypes.append(leaf.dtype)
            sizes.append(int(np.prod(leaf.shape[1:], dtype=np.int64)))
        offsets = tuple(int(o) for o in np.cumsum([0] + sizes[:-1]))
        return cls(treedef=treedef, paths=tuple(paths), shapes=tuple(shapes),
                   dtypes=tuple(dtypes), sizes=tuple(sizes), offsets=offsets,
                   order=order, dtype=dtype)

    # ------------------------------------------------------------------ #

    def flatten(self, stacked: Pytree) -> jax.Array:
        """Stacked pytree -> ``(m, N)`` matrix in canonical column order.

        Value path: only dtypes exactly representable in the arena dtype are
        accepted (for fp32 arenas: float32/bfloat16/float16), so
        ``unflatten(flatten(x)) == x`` bit for bit.  The *bit*-pattern view
        for fingerprinting (``flatten_u32``) is separate and permissive.
        """
        leaves = jax.tree_util.tree_leaves(stacked)
        for pos, i in enumerate(self.order):
            # the leaf's own dtype — jnp.asarray would silently demote f64
            # (x64 disabled) before the guard could see it
            dt = np.dtype(getattr(leaves[i], "dtype", None)
                          or np.asarray(leaves[i]).dtype)
            if not jnp.issubdtype(dt, jnp.floating) or dt.itemsize > \
                    jnp.dtype(self.dtype).itemsize:
                raise TypeError(
                    f"arena leaf {self.paths[pos]} has dtype {dt}, not "
                    f"exactly representable in the "
                    f"{jnp.dtype(self.dtype).name} arena")
        m = leaves[0].shape[0]
        cols = [leaves[i].astype(self.dtype).reshape(m, -1) for i in self.order]
        return jnp.concatenate(cols, axis=1)

    def flatten_u32(self, stacked: Pytree) -> jax.Array:
        """Stacked pytree -> ``(m, N)`` uint32 bit matrix (fingerprint input).

        Non-32-bit leaves are cast to float32 first, exactly like the
        original ``kernels.fingerprint.stack_flatten_u32``.
        """
        leaves = jax.tree_util.tree_leaves(stacked)
        m = leaves[0].shape[0]
        cols = []
        for i in self.order:
            leaf = leaves[i]
            if leaf.dtype.itemsize != 4:
                leaf = leaf.astype(jnp.float32)
            cols.append(jax.lax.bitcast_convert_type(leaf, jnp.uint32)
                        .reshape(m, -1))
        return jnp.concatenate(cols, axis=1)

    def unflatten(self, flat: jax.Array) -> Pytree:
        """``(m, N)`` matrix -> stacked pytree (exact inverse of flatten)."""
        m = flat.shape[0]
        tree_order: list = [None] * len(self.order)
        for pos, i in enumerate(self.order):
            col = flat[:, self.offsets[pos]: self.offsets[pos] + self.sizes[pos]]
            tree_order[i] = col.reshape((m,) + self.shapes[pos]) \
                               .astype(self.dtypes[pos])
        return jax.tree_util.tree_unflatten(self.treedef, tree_order)


def bitcast_u32(rows: jax.Array) -> jax.Array:
    """Arena rows (fp32) -> their exact uint32 bit pattern (fingerprint view)."""
    return jax.lax.bitcast_convert_type(rows, jnp.uint32)


class ParamArena:
    """The population parameter matrix plus its recorded layout.

    ``data`` is an ``(n_clients, N_params)`` device array.  The fused round
    engine consumes and returns ``data`` directly (buffer-donated); the
    methods here are thin views for host-side callers and tests.
    """

    def __init__(self, layout: ArenaLayout, data: jax.Array):
        self.layout = layout
        self.data = data

    @classmethod
    def from_stacked(cls, stacked: Pytree, dtype=jnp.float32) -> "ParamArena":
        layout = ArenaLayout.from_stacked(stacked, dtype=dtype)
        return cls(layout, layout.flatten(stacked))

    @property
    def n_clients(self) -> int:
        return int(self.data.shape[0])

    @property
    def n_params(self) -> int:
        return self.layout.n_params

    # ------------------------------------------------------------------ #

    def gather(self, cohort) -> jax.Array:
        """Rows for a cohort of client ids -> ``(k, N)``."""
        return self.data[jnp.asarray(cohort)]

    def masked_scatter(self, cohort, mask, rows: jax.Array) -> None:
        """Write ``rows`` back into the cohort's slots where ``mask`` is set;
        masked-out slots (stragglers, dropouts) keep their existing params.
        Fixed-shape: the update is a ``where`` over the full cohort, never a
        dynamically-sized row subset."""
        idx = jnp.asarray(cohort)
        keep = jnp.asarray(mask).astype(bool)[:, None]
        upd = jnp.where(keep, rows, self.data[idx])
        self.data = self.data.at[idx].set(upd)

    def rebind(self, flat: jax.Array) -> None:
        """Install a freshly computed (n, N) population matrix (host-side
        entry; the hot path donates ``data`` through the engine instead)."""
        self.data = flat

    def as_pytree(self, rows: jax.Array | None = None) -> Pytree:
        """Pytree view of ``rows`` (default: the whole population)."""
        return self.layout.unflatten(self.data if rows is None else rows)

    def row_pytree(self, i: int) -> Pytree:
        """One client's (unstacked) param pytree."""
        return jax.tree_util.tree_map(
            lambda x: x[0], self.as_pytree(self.data[i][None]))


class ShardedParamArena(ParamArena):
    """A :class:`ParamArena` whose ``(n, N)`` matrix is row-sharded across a
    1-D device mesh on the client axis (`repro.launch.mesh.make_client_mesh`).

    Population state is the O(n_clients · N_params) scaling wall; the cohort
    working set is only O(k · N).  The arena rows spread over the mesh (each
    device holds ``n_padded / shards`` rows), and the round engine shards the
    *cohort* axis over the same mesh: the gather lands each device its own
    cohort slice (never a replicated (k, N) block), local training and
    batched fingerprints run shard-local, and aggregation combines
    shard-local partials with fixed-order tree reductions
    (`repro.core.aggregation`) whose bits do not depend on the partition
    layout — so the full arena never materialises on one device AND seeded
    replay stays bit-identical to the unsharded engine.  The masked
    scatter-back writes only the rows each device owns.

    Rows are zero-padded up to a multiple of the shard count, so every
    device holds an equal row block; padding rows sit beyond every
    real client id, are never gathered or scattered, and ``n_clients`` /
    ``as_pytree`` expose only the logical population.

    Scope of the "never on one device" invariant: it covers the ROUND LOOP —
    every donated step consumes and produces the row-sharded matrix.  The
    host-side entry points (``from_stacked``, ``rebind``, the driver's
    ``params`` setter and async end-of-run broadcast) still build the full
    matrix once on the default device before ``device_put`` redistributes
    it, because the stacked *source* pytree they flatten is itself
    single-device.  Sharded population *initialisation* (per-shard
    ``make_array_from_callback`` fed by a sharded init) is the next scaling
    rung — see ROADMAP.
    """

    def __init__(self, layout: ArenaLayout, data: jax.Array, n_clients: int,
                 mesh):
        from jax.sharding import NamedSharding, PartitionSpec
        super().__init__(layout, data)
        self._n_clients = int(n_clients)
        self.mesh = mesh
        axis = mesh.axis_names[0]
        self.sharding = NamedSharding(mesh, PartitionSpec(axis))
        self.replicated = NamedSharding(mesh, PartitionSpec())
        if data.shape[0] % mesh.devices.size:
            raise ValueError(
                f"padded arena rows ({data.shape[0]}) not divisible by the "
                f"{mesh.devices.size}-device client mesh")
        self.data = jax.device_put(data, self.sharding)

    @classmethod
    def from_stacked(cls, stacked: Pytree, mesh, dtype=jnp.float32
                     ) -> "ShardedParamArena":
        layout = ArenaLayout.from_stacked(stacked, dtype=dtype)
        flat = layout.flatten(stacked)
        n = flat.shape[0]
        return cls(layout, cls._pad_rows(flat, n, mesh), n, mesh)

    @staticmethod
    def _pad_rows(flat: jax.Array, n_clients: int, mesh) -> jax.Array:
        shards = mesh.devices.size
        n_padded = -(-n_clients // shards) * shards
        if n_padded != flat.shape[0]:
            flat = jnp.concatenate(
                [flat, jnp.zeros((n_padded - flat.shape[0], flat.shape[1]),
                                 flat.dtype)])
        return flat

    # ------------------------------------------------------------------ #

    @property
    def n_clients(self) -> int:          # logical population, not padded rows
        return self._n_clients

    @property
    def n_padded(self) -> int:
        return int(self.data.shape[0])

    def rebind(self, flat: jax.Array) -> None:
        """Install a freshly computed (n, N) population matrix, re-padding and
        re-placing it onto the mesh (host-side entry; the hot path donates
        ``data`` through the engine instead)."""
        self.data = jax.device_put(
            self._pad_rows(flat, self._n_clients, self.mesh), self.sharding)

    def as_pytree(self, rows: jax.Array | None = None) -> Pytree:
        if rows is None:
            rows = self.data[: self._n_clients]      # drop padding rows
        return self.layout.unflatten(rows)

    def per_device_bytes(self) -> int:
        """Arena bytes resident on ONE device (the scaling headline)."""
        shard = self.data.addressable_shards[0].data
        return int(np.prod(shard.shape) * shard.dtype.itemsize)
