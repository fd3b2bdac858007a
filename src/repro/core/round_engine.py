"""Flat-buffer FAVAS round engine.

The FAVAS server round is memory-bound: every byte of every resident
client's parameters crosses HBM each round (eq. 3 reweight, line-10
aggregation, line-11/12 selected-client reset). The seed implementation did
that as ~6 separate full-parameter ``tree_map`` passes per round. This
engine instead:

* flattens the parameter pytree ONCE into contiguous flat buffers — a
  ``(Dp,)`` server vector and ``(n, Dp)`` clients / inits matrices per
  dtype bucket, pre-padded to the kernel lane tile so the Pallas path never
  re-pads — and holds them across rounds;
* runs the whole aggregation + reset as ONE streamed pass per tile through
  the multi-output Pallas kernel ``kernels.favas_agg.favas_fused_pallas``
  (TPU; interpret for validation) or its jnp oracle
  ``kernels.ref.favas_fused_ref`` (CPU default — XLA fuses the flat-buffer
  expression into a single loop, which is already the oracle's point);
* unflattens only at the boundaries that need model structure: the vmapped
  local-SGD step (which needs the pytree for the model's loss), evaluation,
  and checkpoint export.

``core.favas.favas_round`` keeps the seed's pytree API by wrapping
``engine_round`` with flatten/unflatten at the call boundary;
``launch.train`` uses ``RoundEngine`` directly so the buffers genuinely
persist across rounds and the jitted round donates them.

Beyond the fused single round, ``engine_multi_round`` /
``RoundEngine.run`` scan a whole CHUNK of rounds on-device — one jitted,
buffer-donating dispatch and one stacked metrics fetch per chunk instead
of per round ("supersteps", docs/architecture.md §7) — which removes the
per-round host dispatch + sync overhead that dominates FAVAS's cheap,
frequent server rounds.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import sampler, reweight
from repro.core.paging import PassthroughCodec, make_codec
from repro.core.quant import quantize_tree
from repro.kernels.favas_agg import CLIENT_TILE, TILE
from repro.kernels.ops import favas_fused_flat, favas_stream_flat
from repro.utils.tree import tree_map


# ---------------------------------------------------------------------------
# FlatSpec: static description of the pytree <-> flat-buffer mapping
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Static (hashable, trace-free) layout of a parameter pytree flattened
    into one contiguous buffer per (leaf dtype, sharding group) "bucket".

    Leaves keep their original dtype; mixed-precision trees get one buffer
    per dtype so no storage precision is lost. Buffer length is padded up to
    a multiple of the kernel lane tile; the padded tail is zero-initialized
    and provably stays zero under the fused round update (the masked padded
    "server" tail aggregates only zeros).

    When built with ``n_clients``, the spec is client-aware: stacked buffers
    additionally pad the client (row) axis up to a multiple of the kernel's
    ``client_tile`` once n exceeds one client block, so the tiled kernel
    never re-pads either axis. Padded rows are all-zero with zero selection
    mask and unit alpha — they contribute exactly nothing to the masked
    aggregation and provably stay zero across rounds.

    When built with ``mesh`` (or explicit ``shard_axes``/``model_shards``),
    the spec is additionally *sharding-aware* (docs/architecture.md §6):
    leaves whose resolved PartitionSpec (``sharding/rules.py``) puts a dim
    on the "model" mesh axis land in a separate bucket per dtype, laid out
    SHARD-MAJOR — the flat buffer is the concatenation over the S model
    shards of that shard's slice of every leaf, each per-shard segment
    independently padded to the lane tile. Partitioning the flat axis into S
    equal contiguous blocks (``PartitionSpec("model")``) therefore hands
    each device exactly its own leaf shards: flatten, the fused round, and
    unflatten all stay communication-free on the model axis (no full-buffer
    all-gather; see ``fused_bucket_update``). Invariant:
    ``bucket_padded[b] == bucket_shards[b] * bucket_shard_padded[b]``.
    """
    treedef: Any
    shapes: tuple                 # per leaf, original shape
    dtypes: tuple                 # per leaf, jnp dtype name (str, hashable)
    bucket_of: tuple              # per leaf, bucket index
    offsets: tuple                # per leaf, start offset within its bucket
    #                               (per-shard units for sharded buckets)
    bucket_dtypes: tuple          # per bucket, dtype name
    bucket_sizes: tuple           # per bucket, unpadded element count (total)
    bucket_padded: tuple          # per bucket, padded element count (total)
    n_clients: Optional[int] = None   # logical client rows (None: not stacked)
    n_padded: Optional[int] = None    # stored client rows incl. padding
    client_tile: Optional[int] = None  # kernel client-axis tile
    shard_axes: tuple = ()        # per leaf, model-sharded dim index or None
    bucket_shards: tuple = ()     # per bucket, model shard count (1 = replicated)
    bucket_shard_sizes: tuple = ()   # per bucket, unpadded elements PER SHARD
    bucket_shard_padded: tuple = ()  # per bucket, padded elements PER SHARD
    mesh_axis: Optional[str] = None  # mesh axis sharded buckets live on
    # residency axis (docs/architecture.md §9): "dense" keeps all n client
    # rows in full precision; "paged" keeps a hot working set of s_max rows
    # plus a codec-encoded cold pool covering all n clients
    residency: str = "dense"
    s_max: Optional[int] = None        # hot rows (logical), paged specs only
    s_hot_padded: Optional[int] = None  # hot rows incl. client-tile padding
    cold_codec: Any = None             # hashable codec (core.paging)
    # cold-pool placement (docs/architecture.md §13): "device" keeps the
    # encoded pools in HBM (the §9 layout); "host" keeps them in host
    # memory — device-resident bytes then scale with s_max instead of n,
    # and each chunk streams only its churned pages through a bounded slab
    cold_placement: str = "device"

    @property
    def n_buckets(self) -> int:
        return len(self.bucket_dtypes)

    def shards(self, b: int) -> int:
        """Model shard count of bucket ``b`` (1 for pre-sharding specs)."""
        return self.bucket_shards[b] if self.bucket_shards else 1

    @property
    def paged(self) -> bool:
        return self.residency == "paged"

    @property
    def stacked_logical(self) -> Optional[int]:
        """Logical rows of the client/init stacks the state carries: the hot
        working set for paged specs, all clients for dense ones."""
        return self.s_max if self.paged else self.n_clients

    @property
    def stacked_rows(self) -> Optional[int]:
        """Stored rows of the client/init stacks (incl. client-tile pad)."""
        return self.s_hot_padded if self.paged else self.n_padded


def make_flat_spec(tree, *, tile: int = TILE, n_clients: Optional[int] = None,
                   client_tile: int = CLIENT_TILE, mesh=None,
                   shard_axes: Optional[Sequence] = None,
                   model_shards: Optional[int] = None,
                   residency: str = "dense", s_max: Optional[int] = None,
                   cold_codec=None, cold_placement: str = "device") -> FlatSpec:
    """Build the layout from a pytree of arrays / ShapeDtypeStructs.

    ``n_clients``: make the spec client-aware (see class docstring). Row
    padding only kicks in beyond one client block (n > client_tile), so
    small federations carry no extra rows.

    ``mesh``: make the spec sharding-aware — leaves are classified through
    ``sharding.rules.model_shard_axes`` (the same regex rules pjit uses)
    and model-sharded leaves get their own shard-major bucket per dtype.
    ``shard_axes`` (a per-leaf list of dim indices / None, aligned with
    ``tree_leaves``) overrides the rule lookup; ``model_shards`` overrides
    the shard count (needed when passing ``shard_axes`` without a mesh —
    layout is pure metadata and never touches devices). A leaf whose
    nominated dim does not divide by the shard count falls back to the
    replicated bucket, mirroring ``sharding.rules.check_divisible``.

    ``residency="paged"``: virtualize the client axis (docs/architecture.md
    §9) — the state's stacks hold only ``s_max`` hot rows (padded with the
    same client-tile formula as the dense n), and a ``cold_codec``-encoded
    pool covers all n clients. ``s_max`` defaults to (and is clamped at)
    ``n_clients``; at ``s_max == n_clients`` the hot set is the whole
    id-ordered population and the paged round is bit-exact with the dense
    one. ``cold_codec`` defaults to the passthrough (identity) codec.

    ``cold_placement="host"`` (paged specs only, docs/architecture.md §13)
    moves the encoded cold pools to HOST memory: the state carries a
    ``core.streaming.HostColdPool`` instead of device arrays, every round
    touches cold pages through a churn-bounded device slab planned ahead
    of the chunk, and device-resident bytes scale with ``s_max`` instead
    of ``n``. Values are bit-exact vs ``"device"`` placement — only where
    the encoded bytes live changes."""
    if cold_placement not in ("device", "host"):
        raise ValueError(f"unknown cold_placement {cold_placement!r}")
    if cold_placement == "host" and residency != "paged":
        raise ValueError("cold_placement='host' requires residency='paged'")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    S0 = model_shards or 1
    if mesh is not None and model_shards is None:
        S0 = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    if shard_axes is None:
        if mesh is not None and S0 > 1:
            from repro.sharding.rules import model_shard_axes  # lazy: no cycle
            shard_axes = model_shard_axes(tree, mesh)
        else:
            shard_axes = [None] * len(leaves)
    if len(shard_axes) != len(leaves):
        raise ValueError(
            f"shard_axes has {len(shard_axes)} entries for {len(leaves)} leaves")
    shapes, dtypes, bucket_of, offsets, axes_out = [], [], [], [], []
    keys, bucket_dtypes, shards_l, cursors = [], [], [], []
    for leaf, ax in zip(leaves, shard_axes):
        dt = jnp.dtype(leaf.dtype).name
        size = 1
        for d in leaf.shape:
            size *= int(d)
        if (ax is not None and (S0 <= 1 or ax >= len(leaf.shape)
                                or leaf.shape[ax] % S0 != 0)):
            ax = None                    # non-dividing dim: replicate
        key = (dt, ax is not None)
        if key not in keys:
            keys.append(key)
            bucket_dtypes.append(dt)
            shards_l.append(S0 if ax is not None else 1)
            cursors.append(0)
        b = keys.index(key)
        shapes.append(tuple(leaf.shape))
        dtypes.append(dt)
        bucket_of.append(b)
        offsets.append(cursors[b])
        cursors[b] += size // shards_l[b]
        axes_out.append(ax)
    shard_padded = tuple(c + ((-c) % tile) for c in cursors)
    padded = tuple(sp * s for sp, s in zip(shard_padded, shards_l))
    sizes = tuple(c * s for c, s in zip(cursors, shards_l))
    n_padded = None
    if n_clients is not None:
        n_padded = (n_clients if n_clients <= client_tile
                    else n_clients + ((-n_clients) % client_tile))
    s_hot_padded = None
    if residency == "paged":
        if n_clients is None:
            raise ValueError("residency='paged' requires n_clients")
        s_max = n_clients if s_max is None else min(int(s_max), n_clients)
        if s_max < 1:
            raise ValueError(f"s_max must be >= 1 (got {s_max})")
        # same padding formula as the dense client axis, so at s_max == n
        # the hot stacks have exactly the dense shapes (the parity regime)
        s_hot_padded = (s_max if s_max <= client_tile
                        else s_max + ((-s_max) % client_tile))
        cold_codec = cold_codec if cold_codec is not None else PassthroughCodec()
    else:
        s_max, cold_codec = None, None
    return FlatSpec(treedef=treedef, shapes=tuple(shapes), dtypes=tuple(dtypes),
                    bucket_of=tuple(bucket_of), offsets=tuple(offsets),
                    bucket_dtypes=tuple(bucket_dtypes),
                    bucket_sizes=sizes, bucket_padded=padded,
                    n_clients=n_clients, n_padded=n_padded,
                    client_tile=client_tile if n_clients is not None else None,
                    shard_axes=tuple(axes_out),
                    bucket_shards=tuple(shards_l),
                    bucket_shard_sizes=tuple(cursors),
                    bucket_shard_padded=shard_padded,
                    mesh_axis="model" if any(s > 1 for s in shards_l) else None,
                    residency=residency, s_max=s_max,
                    s_hot_padded=s_hot_padded, cold_codec=cold_codec,
                    cold_placement=(cold_placement if residency == "paged"
                                    else "device"))


def flatten_tree(spec: FlatSpec, tree) -> tuple:
    """Pytree -> tuple of (Dp_b,) flat buffers (one per spec bucket).

    Sharded buckets are laid out shard-major: leaf dims sharded on the model
    axis move to the front and split into S rows before concatenation, so
    every op here is shard-local under GSPMD (transpose + reshape of the
    sharded dim by exactly the shard count — no cross-device data motion)."""
    leaves = jax.tree_util.tree_leaves(tree)
    parts = [[] for _ in range(spec.n_buckets)]
    for leaf, b, ax in zip(leaves, spec.bucket_of, spec.shard_axes):
        S = spec.shards(b)
        if S > 1:
            parts[b].append(jnp.moveaxis(leaf, ax, 0).reshape(S, -1))
        else:
            parts[b].append(jnp.ravel(leaf))
    out = []
    for b in range(spec.n_buckets):
        S = spec.shards(b)
        if S > 1:
            buf = (jnp.concatenate(parts[b], axis=1) if len(parts[b]) > 1
                   else parts[b][0])
            pad = spec.bucket_shard_padded[b] - spec.bucket_shard_sizes[b]
            if pad:
                buf = jnp.pad(buf, ((0, 0), (0, pad)))
            out.append(buf.reshape(-1))
        else:
            buf = jnp.concatenate(parts[b]) if len(parts[b]) > 1 else parts[b][0]
            pad = spec.bucket_padded[b] - spec.bucket_sizes[b]
            if pad:
                buf = jnp.pad(buf, (0, pad))
            out.append(buf)
    return tuple(out)


def flatten_stacked(spec: FlatSpec, tree) -> tuple:
    """Client-stacked pytree (leading axis n) -> tuple of (Np_b, Dp_b).

    With a client-aware spec the row axis is zero-padded up to
    ``spec.n_padded`` so the tiled kernel path never re-pads."""
    leaves = jax.tree_util.tree_leaves(tree)
    n = leaves[0].shape[0]
    rpad = 0
    if spec.stacked_rows is not None:
        # loud failure instead of silently mis-padding: a client-aware spec
        # only describes trees with exactly stacked_logical rows (n_clients
        # dense, the s_max hot working set paged)
        if n != spec.stacked_logical:
            raise ValueError(
                f"stacked tree has {n} client rows but the spec stacks "
                f"{spec.stacked_logical} ({spec.residency})")
        rpad = spec.stacked_rows - n
    parts = [[] for _ in range(spec.n_buckets)]
    for leaf, b, ax in zip(leaves, spec.bucket_of, spec.shard_axes):
        S = spec.shards(b)
        if S > 1:
            parts[b].append(jnp.moveaxis(leaf, 1 + ax, 1).reshape(n, S, -1))
        else:
            parts[b].append(leaf.reshape(n, -1))
    out = []
    for b in range(spec.n_buckets):
        S = spec.shards(b)
        if S > 1:
            buf = (jnp.concatenate(parts[b], axis=2) if len(parts[b]) > 1
                   else parts[b][0])
            pad = spec.bucket_shard_padded[b] - spec.bucket_shard_sizes[b]
            if pad or rpad:
                buf = jnp.pad(buf, ((0, rpad), (0, 0), (0, pad)))
            out.append(buf.reshape(n + rpad, spec.bucket_padded[b]))
        else:
            buf = (jnp.concatenate(parts[b], axis=1) if len(parts[b]) > 1
                   else parts[b][0])
            pad = spec.bucket_padded[b] - spec.bucket_sizes[b]
            if pad or rpad:
                buf = jnp.pad(buf, ((0, rpad), (0, pad)))
            out.append(buf)
    return tuple(out)


def unflatten_tree(spec: FlatSpec, bufs: Sequence):
    """Tuple of (Dp_b,) buffers -> pytree with the original leaf layout.
    Sharded buckets invert the shard-major layout (shard-local under GSPMD,
    exact inverse of ``flatten_tree`` — round-trips are bit-exact)."""
    leaves = []
    for shape, dt, b, off, ax in zip(spec.shapes, spec.dtypes, spec.bucket_of,
                                     spec.offsets, spec.shard_axes):
        size = 1
        for d in shape:
            size *= d
        S = spec.shards(b)
        if S > 1:
            rows = bufs[b].reshape(S, spec.bucket_shard_padded[b])
            rows = jax.lax.dynamic_slice_in_dim(rows, off, size // S, axis=1)
            moved = (shape[ax],) + shape[:ax] + shape[ax + 1:]
            leaves.append(jnp.moveaxis(rows.reshape(moved), 0, ax))
        else:
            leaves.append(jax.lax.dynamic_slice_in_dim(bufs[b], off, size)
                          .reshape(shape))
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


def unflatten_stacked(spec: FlatSpec, bufs: Sequence):
    """Tuple of (Np_b, Dp_b) buffers -> client-stacked pytree (padded client
    rows, if any, are dropped)."""
    leaves = []
    for shape, dt, b, off, ax in zip(spec.shapes, spec.dtypes, spec.bucket_of,
                                     spec.offsets, spec.shard_axes):
        buf = bufs[b]
        n = buf.shape[0]
        if spec.stacked_rows is not None:
            if n != spec.stacked_rows:
                raise ValueError(
                    f"stacked buffer has {n} rows but the spec stores "
                    f"{spec.stacked_rows} ({spec.residency})")
            if spec.stacked_logical < n:
                n = spec.stacked_logical
                buf = buf[:n]
        size = 1
        for d in shape:
            size *= d
        S = spec.shards(b)
        if S > 1:
            rows = buf.reshape(n, S, spec.bucket_shard_padded[b])
            rows = jax.lax.dynamic_slice_in_dim(rows, off, size // S, axis=2)
            moved = (n, shape[ax]) + shape[:ax] + shape[ax + 1:]
            leaves.append(jnp.moveaxis(rows.reshape(moved), 1, 1 + ax))
        else:
            leaves.append(
                jax.lax.dynamic_slice_in_dim(buf, off, size, axis=1)
                .reshape((n,) + shape))
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


def pad_client_vec(spec: FlatSpec, v, fill: float = 0.0):
    """(n,) per-client vector -> (Np,) padded to the spec's stored rows.
    ``fill``: value for padded rows (0 for masks — padded rows are never
    selected; 1 for alphas — keeps the guarded division trivially exact)."""
    if spec.stacked_rows is None:
        return v
    if v.shape[0] != spec.stacked_logical:
        raise ValueError(
            f"per-client vector has {v.shape[0]} rows but the spec stacks "
            f"{spec.stacked_logical} ({spec.residency})")
    rpad = spec.stacked_rows - spec.stacked_logical
    if not rpad:
        return v
    return jnp.concatenate([v, jnp.full((rpad,), fill, v.dtype)])


def stack_server_rows(spec: FlatSpec, server_bufs: Sequence, n: int) -> tuple:
    """Server flat buffers -> client/init row stacks: the server row
    broadcast to n clients plus all-zero padded rows up to the spec's stored
    row count. Each result is a DISTINCT buffer (broadcasts are materialized)
    so a donating jit never sees the same buffer twice."""
    if spec.stacked_logical is not None and n != spec.stacked_logical:
        raise ValueError(
            f"stacking {n} client rows but the spec stacks "
            f"{spec.stacked_logical} ({spec.residency})")
    rows = spec.stacked_rows or n
    out = []
    for b in server_bufs:
        buf = jnp.broadcast_to(b[None], (n,) + b.shape)
        buf = (jnp.pad(buf, ((0, rows - n), (0, 0))) if rows > n
               else buf.copy())
        out.append(buf)
    return tuple(out)


# ---------------------------------------------------------------------------
# Mesh-aware execution: shardings, constraints, and the per-bucket fused call
# ---------------------------------------------------------------------------

def bucket_partition_specs(spec: FlatSpec, *, stacked: bool) -> tuple:
    """Per-bucket ``PartitionSpec`` for flat buffers: sharded buckets put the
    lane axis on the spec's model mesh axis, replicated buckets on nothing.
    ``stacked``: (n, Dp) client/init matrices (leading client axis is NOT
    model-sharded) vs (Dp,) server vectors."""
    from jax.sharding import PartitionSpec as P
    out = []
    for b in range(spec.n_buckets):
        ax = spec.mesh_axis if spec.shards(b) > 1 else None
        out.append(P(None, ax) if stacked else P(ax))
    return tuple(out)


def engine_sharding(spec: FlatSpec, mesh):
    """``NamedSharding`` pytree for an :class:`EngineState` on ``mesh`` —
    what ``jax.device_put`` of the initial state and the jitted round's
    output constraints use. Sharded buckets live with their lane axis on
    "model"; counters/stale/key/t are replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    srv = tuple(NamedSharding(mesh, p)
                for p in bucket_partition_specs(spec, stacked=False))
    stk = tuple(NamedSharding(mesh, p)
                for p in bucket_partition_specs(spec, stacked=True))
    hot_ids, cold = None, None
    if spec.paged:
        hot_ids = rep
    if spec.paged and spec.cold_placement == "device":
        # cold pools shard exactly like the dense stacked buckets (§6): the
        # encoded lane axis (packed codes / per-shard scales) splits on the
        # model axis, the client-id row axis replicates. Host-placed pools
        # are NOT device arrays (core.streaming.HostColdPool) and carry no
        # sharding — their churn slab gets these specs per chunk instead.
        cold = tuple(
            jax.tree_util.tree_map(
                lambda p: NamedSharding(mesh, p),
                spec.cold_codec.partition_specs(
                    spec.shards(b) > 1, spec.mesh_axis or "model"),
                is_leaf=lambda x: isinstance(x, P))
            for b in range(spec.n_buckets))
    return EngineState(server=srv, clients=stk, inits=stk,
                       counters=rep, stale=rep, key=rep, t=rep,
                       hot_ids=hot_ids, cold=cold)


def _constrain_buckets(spec: FlatSpec, mesh, bufs, *, stacked: bool) -> tuple:
    """Pin per-bucket flat buffers to their mesh sharding (None entries pass
    through). Keeps GSPMD from replicating the buffers around the
    flatten/unflatten transposes in the round body."""
    if mesh is None:
        return tuple(bufs)
    from jax.sharding import NamedSharding
    specs = bucket_partition_specs(spec, stacked=stacked)
    return tuple(
        x if x is None or spec.shards(b) <= 1
        else jax.lax.with_sharding_constraint(x, NamedSharding(mesh, specs[b]))
        for b, x in enumerate(bufs))


def _constrain_cold(spec: FlatSpec, mesh, cold) -> tuple:
    """Pin per-bucket encoded cold pools to the §6 layout (lane axis on the
    model mesh axis for sharded buckets). Row-axis gathers/scatters and the
    per-shard encode reductions are then provably shard-local — the paged
    round adds no collectives over the dense engine's."""
    if mesh is None:
        return tuple(cold)
    from jax.sharding import NamedSharding, PartitionSpec as P
    out = []
    for b in range(spec.n_buckets):
        if spec.shards(b) <= 1:
            out.append(cold[b])
            continue
        specs = spec.cold_codec.partition_specs(True, spec.mesh_axis or "model")
        out.append(jax.tree_util.tree_map(
            lambda p, x: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, p)),
            specs, cold[b], is_leaf=lambda t: isinstance(t, P)))
    return tuple(out)


def _bucket_dispatch(flat_fn, spec: FlatSpec, b: int, server_b, trained_b,
                     inits_b, alpha_p, mask_p, s: float, *, progress_b,
                     progress_codes_b, progress_bits: int, n_logical, mesh,
                     use_kernel, stacked_outputs: bool):
    """The mesh-aware dispatch shared by :func:`fused_bucket_update` and
    :func:`stream_bucket_update` (docs/architecture.md §6). ``flat_fn`` is
    ``favas_fused_flat`` or ``favas_stream_flat``; ``stacked_outputs`` says
    whether it also returns the (n, Dp) client/init stacks.

    * no mesh -> a plain ``flat_fn`` call (kernel or oracle);
    * kernel on a mesh -> ``shard_map``: each device runs the Pallas kernel
      on its own (n, Dp_b/S) lane slice of a model-sharded bucket, or on
      the whole bucket when it is replicated (the chip's compiler cannot
      partition a Pallas call, so the kernel never runs unwrapped on a
      mesh). Slices are lane-tile aligned by construction (per-shard
      padding), the client reduction is shard-local, and the body contains
      no collectives — the round cannot all-gather the buffer;
    * oracle on a mesh -> the jnp expression; a sharded bucket gets
      explicit output ``PartitionSpec`` constraints so GSPMD partitions
      the elementwise lanes and the (unsharded) client reduction locally.

    ``progress_codes_b`` (mutually exclusive with ``progress_b``): the
    transmitted progress as a ``{"codes", "scale"}`` encoding from
    ``kernels.ops.cold_requant_rows`` at ``progress_bits``, encoded with
    ``shards=spec.shards(b)``. The per-shard scale layout makes the codes-in
    shard_map body exactly per-device: each device's codes slice is a
    standalone shards=1 encoding of its own lane segment, so the kernel
    dequantizes shard-locally with no collectives."""
    if progress_b is not None and progress_codes_b is not None:
        raise ValueError("progress_b and progress_codes_b are mutually "
                         "exclusive")
    common = dict(progress_bits=progress_bits, client_tile=spec.client_tile,
                  n_logical=n_logical)
    if mesh is None:
        return flat_fn(server_b, trained_b, inits_b, alpha_p, mask_p,
                       float(s), progress=progress_b,
                       progress_codes=progress_codes_b,
                       progress_shards=max(1, spec.shards(b)),
                       use_kernel=use_kernel, **common)
    kernel_active = (use_kernel if use_kernel is not None
                     else jax.default_backend() == "tpu")
    from jax.sharding import NamedSharding, PartitionSpec as P
    ax = spec.mesh_axis if spec.shards(b) > 1 else None
    lane, row, vec = P(ax), P(None, ax), P(None)
    out_specs = (lane, row, row) if stacked_outputs else lane
    if kernel_active:
        def body(*ops):
            pr = pc = None
            if progress_b is not None:
                srv, cli, ini, pr, al, mk = ops
            elif progress_codes_b is not None:
                srv, cli, ini, cd, sc, al, mk = ops
                pc = {"codes": cd, "scale": sc}
            else:
                srv, cli, ini, al, mk = ops
            # per-device view: the local codes slice is one shard segment
            # with its own scale column -> progress_shards=1
            return flat_fn(srv, cli, ini, al, mk, float(s), progress=pr,
                           progress_codes=pc, progress_shards=1,
                           use_kernel=True, **common)

        operands = [server_b, trained_b, inits_b]
        in_specs = [lane, row, row]
        if progress_b is not None:
            operands.append(progress_b)
            in_specs.append(row)
        elif progress_codes_b is not None:
            # codes split on the lane axis like the row buffers; the
            # (rows, S) scale splits its shard column onto its shard
            operands += [progress_codes_b["codes"], progress_codes_b["scale"]]
            in_specs += [row, row]
        operands += [alpha_p, mask_p]
        in_specs += [vec, vec]
        return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                             out_specs=out_specs, check_vma=False)(*operands)
    out = flat_fn(server_b, trained_b, inits_b, alpha_p, mask_p, float(s),
                  progress=progress_b, progress_codes=progress_codes_b,
                  progress_shards=spec.shards(b), use_kernel=False, **common)
    if ax is None:
        return out
    return jax.tree_util.tree_map(
        lambda o, p: jax.lax.with_sharding_constraint(o, NamedSharding(mesh, p)),
        out, out_specs, is_leaf=lambda x: isinstance(x, P))


def fused_bucket_update(spec: FlatSpec, b: int, server_b, trained_b, inits_b,
                        alpha_p, mask_p, s: float, *, progress_b=None,
                        progress_codes_b=None, progress_bits: int = 0,
                        n_logical: Optional[int] = None, mesh=None,
                        use_kernel: Optional[bool] = None):
    """One bucket's fused aggregation + selected-client reset, mesh-aware
    (dispatch: :func:`_bucket_dispatch`). Returns (server_new, clients_new,
    inits_new) with the inputs' shardings."""
    return _bucket_dispatch(
        favas_fused_flat, spec, b, server_b, trained_b, inits_b, alpha_p,
        mask_p, s, progress_b=progress_b, progress_codes_b=progress_codes_b,
        progress_bits=progress_bits, n_logical=n_logical, mesh=mesh,
        use_kernel=use_kernel, stacked_outputs=True)


def stream_bucket_update(spec: FlatSpec, b: int, server_b, trained_b, inits_b,
                         alpha_p, mask_p, s: float, *, progress_b=None,
                         progress_codes_b=None, progress_bits: int = 0,
                         n_logical: Optional[int] = None, mesh=None,
                         use_kernel: Optional[bool] = None):
    """One bucket's STREAMED aggregation (docs/architecture.md §13):
    the :func:`fused_bucket_update` dispatch contract, returning ONLY the
    new server vector. The caller applies the selected-client reset as a
    churn-bounded scatter of this row into the donated client/init
    buffers — unselected rows are never rewritten, so per-bucket round
    traffic drops from ~2R+2W to 1R (+ O(s * Dp) scatter writes) per
    resident byte. Bit-identical server to ``fused_bucket_update`` per
    dispatch path."""
    return _bucket_dispatch(
        favas_stream_flat, spec, b, server_b, trained_b, inits_b, alpha_p,
        mask_p, s, progress_b=progress_b, progress_codes_b=progress_codes_b,
        progress_bits=progress_bits, n_logical=n_logical, mesh=mesh,
        use_kernel=use_kernel, stacked_outputs=False)


def _streamed_reset(spec: FlatSpec, mesh, bufs, sel_idx, rows):
    """Churn-bounded selected-client reset: scatter each bucket's new server
    row into the ``sel_idx`` positions of the (donated) state buffers.
    ``rows`` is the per-bucket new-server vector list. XLA performs the
    scatter in place on donated inputs, so unselected rows are never
    rewritten (the write-traffic audit in launch/roofline.py pins this).
    Bit-exact vs the fused reset: the mask is exactly the indicator of
    ``sel_idx`` and the fused ``m*s_new + (1-m)*x`` blend is ``x`` (exact
    f32 round-trip) off-selection and ``s_new.astype(dtype)`` — the
    scattered row — on it."""
    out = [buf.at[sel_idx].set(row.astype(buf.dtype))
           for buf, row in zip(bufs, rows)]
    return _constrain_buckets(spec, mesh, out, stacked=True)


def slab_shardings(spec: FlatSpec, mesh):
    """Per-bucket ``NamedSharding`` tree for a host-tier churn slab — the
    same §6 layout as the device-placed cold pools (encoded lane axis on
    the model mesh axis, row axis replicated). None without a mesh."""
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P
    return tuple(
        jax.tree_util.tree_map(
            lambda p: NamedSharding(mesh, p),
            spec.cold_codec.partition_specs(
                spec.shards(b) > 1, spec.mesh_axis or "model"),
            is_leaf=lambda x: isinstance(x, P))
        for b in range(spec.n_buckets))


def _encode_progress(spec: FlatSpec, trained, inits, k_q, bits: int, *,
                     mesh=None, use_kernel: Optional[bool] = None) -> tuple:
    """Per-bucket LUQ encode of the transmitted progress (``quant_fused``
    transport): ``trained[b] - inits[b]`` in f32 -> packed codes +
    per-(row, shard) scales via ``kernels.ops.cold_requant_rows``. Padded
    client rows and lane tails are zero in both operands, so their delta is
    exactly zero, the guarded scale is 1.0 and the codes decode to exact
    zeros — padding stays a no-op through the codec. Keys: ``fold_in(k_q,
    0x7166)`` ('qf') then per-bucket fold — a stream disjoint from both the
    per-leaf ``quantize_tree`` split and the paged eviction fold."""
    from repro.kernels.ops import cold_requant_rows   # lazy: no import cycle
    k_qf = jax.random.fold_in(k_q, 0x7166)
    codes = []
    for b in range(spec.n_buckets):
        delta = (trained[b].astype(jnp.float32)
                 - inits[b].astype(jnp.float32))
        codes.append(cold_requant_rows(
            delta, bits, jax.random.fold_in(k_qf, b),
            shards=max(1, spec.shards(b)), use_kernel=use_kernel))
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        lane = P(None, spec.mesh_axis or "model")
        codes = [pc if spec.shards(b) <= 1 else jax.tree_util.tree_map(
                     lambda x: jax.lax.with_sharding_constraint(
                         x, NamedSharding(mesh, lane)), pc)
                 for b, pc in enumerate(codes)]
    return tuple(codes)


# ---------------------------------------------------------------------------
# Engine state (flat buffers held across rounds)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EngineState:
    server: tuple                  # per bucket (Dp_b,)
    clients: tuple                 # per bucket (rows, Dp_b) — all n rows on a
    #                                dense spec, the s_max hot rows on paged
    inits: tuple                   # per bucket (rows, Dp_b)
    counters: jnp.ndarray          # (n,) int32 — q^i, local steps since reset
    stale: jnp.ndarray             # (n,) int32 — rounds since last selection
    key: jnp.ndarray
    t: jnp.ndarray                 # scalar int32
    # paged residency only (None on dense states, docs/architecture.md §9):
    hot_ids: Any = None            # (s_max,) int32 resident client ids, sorted
    cold: Any = None               # per bucket codec-encoded pools, n rows

    def tree_flatten(self):
        return ((self.server, self.clients, self.inits, self.counters,
                 self.stale, self.key, self.t, self.hot_ids, self.cold), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def engine_init(spec: FlatSpec, params, cfg, key, *,
                use_kernel: Optional[bool] = None) -> EngineState:
    """Build the initial :class:`EngineState` from a parameter pytree.

    All clients start from the server model (Algorithm 1 line 16): the
    server buffer is ``params`` flattened per ``spec``; the client and init
    stacks are that row broadcast to ``cfg.n_clients`` distinct buffers.
    Client rows beyond ``n`` (the client-tile padding of a client-aware
    spec) are zero and stay zero across rounds; per-shard lane tails of a
    sharding-aware spec are likewise zero forever.

    Args:
      spec: layout from :func:`make_flat_spec` (must be client-aware with
        ``n_clients == cfg.n_clients`` if built with ``n_clients``).
      params: parameter pytree matching ``spec.treedef``.
      cfg: :class:`repro.core.favas.FavasConfig` (reads ``n_clients``).
      key: PRNG key stored in the state and split every round.
      use_kernel: cold-pool codec dispatch for the paged seeding encode —
        same contract as the round's (None = TPU auto); the kernel and
        oracle paths are bit-identical under shared uniforms so the choice
        never changes the seeded state's values.

    Returns an :class:`EngineState` on the default device; on a mesh,
    ``jax.device_put`` it with :func:`engine_sharding` (``RoundEngine``
    does both)."""
    n = cfg.n_clients
    server = flatten_tree(spec, params)
    hot_ids, cold = None, None
    if spec.paged:
        if cfg.s_selected > spec.s_max:
            raise ValueError(
                f"s_selected={cfg.s_selected} exceeds the hot working set "
                f"s_max={spec.s_max}: every selected client must fit hot")
        # hot working set: everyone starts equally fresh (stale 0), so the
        # staleness/id order picks the s_max lowest ids — at s_max == n this
        # is arange(n), the dense layout
        hot_ids = jnp.arange(spec.s_max, dtype=jnp.int32)
        clients = stack_server_rows(spec, server, spec.s_max)
        inits = stack_server_rows(spec, server, spec.s_max)
        # cold pools: every client is the server row with zero progress, so
        # ONE row is encoded per bucket and broadcast to all n ids (for the
        # LUQ codec the progress codes are exactly zero; identical per-row
        # uniforms are harmless since the rows are identical). fold_in keeps
        # the state's key chain untouched — bit-identical to the dense init.
        k_cold = jax.random.fold_in(key, 0x636f6c64)
        cold = []
        for b in range(spec.n_buckets):
            row = server[b][None]
            enc1 = spec.cold_codec.encode_pair(
                row, row, jax.random.fold_in(k_cold, b),
                shards=spec.shards(b), use_kernel=use_kernel)
            if spec.cold_placement == "host":
                # host tier (§13): the encode still runs on device (bit-
                # identical bytes to the device placement) but the n-row
                # broadcast materializes in HOST memory — the device never
                # holds an O(n) pool
                import numpy as np
                cold.append(jax.tree_util.tree_map(
                    lambda a: np.broadcast_to(
                        np.asarray(jax.device_get(a)),
                        (n,) + a.shape[1:]).copy(), enc1))
            else:
                cold.append(jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a, (n,) + a.shape[1:]).copy(),
                    enc1))
        if spec.cold_placement == "host":
            from repro.core.streaming import HostColdPool  # lazy: no cycle
            cold = HostColdPool(tuple(cold))
        else:
            cold = tuple(cold)
    else:
        clients = stack_server_rows(spec, server, n)
        inits = stack_server_rows(spec, server, n)
    # private copy of the key: the jitted round DONATES the state, and a
    # caller-owned key array shared between two states (or reused for a
    # second init) would be deleted by the first state's first dispatch
    return EngineState(
        server=server, clients=clients, inits=inits,
        counters=jnp.zeros((n,), jnp.int32),
        stale=jnp.zeros((n,), jnp.int32),
        key=jnp.array(key, copy=True), t=jnp.zeros((), jnp.int32),
        hot_ids=hot_ids, cold=cold)


# ---------------------------------------------------------------------------
# The round
# ---------------------------------------------------------------------------

def _local_training(loss_fn: Callable, cfg, clients_tree, counters,
                    new_counters, batch):
    """Masked R-step local SGD, vmapped over the client axis.

    Returns (trained_tree, loss_sum (n,), live_steps (n,)) — the raw masked
    loss sum and live-step count per client, so the caller can form a
    live-step-weighted aggregate instead of averaging in idle clients.

    batch: pytree with leading dims (n, R, ...) — one microbatch per client
    per potential local step."""

    def one_client(params, data, q0, q1):
        def step(p, inp):
            k, batch_k = inp
            loss, g = jax.value_and_grad(loss_fn)(p, batch_k)
            live = ((q0 + k) < q1).astype(jnp.float32)
            # update in f32, store back in the leaf dtype: keeps the scan
            # carry type stable for bf16 leaves (f32 leaves are unchanged —
            # the expression is the same f32 arithmetic as before)
            p = tree_map(
                lambda pp, gg: (pp - cfg.eta * live * gg.astype(jnp.float32)
                                ).astype(pp.dtype),
                p, g)
            return p, loss * live
        ks = jnp.arange(cfg.R)
        params, losses = jax.lax.scan(step, params, (ks, data))
        return params, jnp.sum(losses), (q1 - q0).astype(jnp.float32)

    return jax.vmap(one_client)(clients_tree, batch, counters, new_counters)


def engine_round(spec: FlatSpec, state: EngineState, batch=None, *, cfg,
                 loss_fn: Callable, lambdas,
                 det_alpha: Optional[jnp.ndarray] = None,
                 use_kernel: Optional[bool] = None, mesh=None,
                 quant_fused: bool = False, corpus=None, batch_key=None,
                 schedule: str = "streamed", slab=None, plan=None):
    """One FAVAS server round on flat buffers. Pure; jit/pjit this.

    The hot path is: unflatten clients -> vmapped local SGD -> flatten ->
    ONE fused aggregation+reset pass per bucket. No per-leaf tree_map
    touches the aggregation.

    Args:
      spec: the :func:`make_flat_spec` layout the buffers follow.
      state: current :class:`EngineState`; donate it when jitting.
      batch: pytree with leading dims (n, R, ...) — one microbatch per
        client per potential local step.
      cfg: :class:`FavasConfig` (n_clients, s_selected, local_steps, eta,
        reweight, quant_bits).
      loss_fn: ``loss_fn(params_pytree, microbatch) -> scalar``; vmapped
        over the client axis inside.
      lambdas: (n,) per-client heterogeneity rates for the step sampler.
      det_alpha: (n,) deterministic eq. 3 coefficients (used when
        ``cfg.reweight == "deterministic"``).
      use_kernel: None -> Pallas kernel on TPU / jnp oracle elsewhere;
        True/False force the choice (True runs interpret mode off-TPU).
      quant_fused: FAVAS[QNN] transport format. False (default, the seed
        semantics) quantizes the transmitted progress in tree space with
        per-leaf scales and hands the fused pass a dense dequantized
        (n, Dp) buffer. True encodes the progress per BUCKET as bit-packed
        LUQ codes + per-(row, shard) scales (``kernels.ops.
        cold_requant_rows``) and hands the fused pass the CODES — the
        kernel dequantizes per VMEM tile, so no full-precision (n, Dp)
        progress buffer ever materializes (different per-row-vs-per-leaf
        scale granularity and key stream, so an opt-in knob, not a drop-in
        replacement for the seed path).
      mesh: optional device mesh matching a sharding-aware ``spec``. Sharded
        buckets then run their fused pass via :func:`fused_bucket_update`
        (shard_map on the kernel path, pjit constraints on the oracle path)
        so the round never gathers a full buffer onto one device.
      corpus / batch_key: device data plane — instead of ``batch``, a
        resident :class:`repro.data.device_corpus.DeviceCorpus` plus the
        round's batch key; the round samples its own minibatches (and, on a
        paged spec, gathers corpus rows for the hot working set only).
      schedule: "streamed" (default, docs/architecture.md §13) aggregates
        with the single-sweep :func:`stream_bucket_update` and resets the
        s selected rows by a churn-bounded scatter into the donated
        buffers (~1R+1W per resident byte, no pass-through rewrites);
        "two_sweep" keeps the historical fused aggregation+reset kernel
        (~2R+2W). The two schedules are BIT-EXACT — the mask is exactly
        the indicator of the Gumbel top-s index set — so the knob only
        changes traffic, never values.
      slab / plan: host-tier cold paging (paged specs with
        ``cold_placement="host"`` only): the chunk's churned cold pages as
        a device slab plus this round's slab positions — see
        :func:`plan_rounds` and ``core.streaming``. The round then returns
        ``(new_state, new_slab, metrics)``.

    On a ``residency="paged"`` spec the round runs the hot/cold body
    (:func:`_paged_round`): select -> promote/evict the hot working set ->
    gather+dequant -> fused round over the s_max hot rows -> requant+
    scatter-back. With the passthrough codec at ``s_max == n`` it is
    bit-exact with this dense body (tests/test_paged_engine.py).

    Returns ``(new_state, metrics)`` where metrics holds the live-step-
    weighted ``loss``, ``mean_steps``, ``selected`` and ``stale_rounds``."""
    if schedule not in ("streamed", "two_sweep"):
        raise ValueError(f"unknown schedule {schedule!r}")
    if spec.paged:
        return _paged_round(spec, state, batch, cfg=cfg, loss_fn=loss_fn,
                            lambdas=lambdas, det_alpha=det_alpha,
                            use_kernel=use_kernel, mesh=mesh,
                            quant_fused=quant_fused,
                            corpus=corpus, batch_key=batch_key,
                            schedule=schedule, slab=slab, plan=plan)
    if slab is not None or plan is not None:
        raise ValueError("slab/plan are host-tier paging arguments "
                         "(paged specs with cold_placement='host')")
    if corpus is not None:
        batch = corpus.sample_round_batch(batch_key, cfg.R)
    n, s, K = cfg.n_clients, cfg.s_selected, cfg.local_steps
    key, k_inc, k_sel, k_q = jax.random.split(state.key, 4)

    # 1. heterogeneous progress this round
    d = sampler.sample_increments(k_inc, lambdas)              # (n,)
    new_counters = jnp.minimum(state.counters + d, K)

    # 2. masked local SGD (needs model structure -> tree space)
    clients_tree = unflatten_stacked(spec, state.clients)
    trained_tree, loss_sum, live = _local_training(
        loss_fn, cfg, clients_tree, state.counters, new_counters, batch)

    # 3. eq. (3) reweight coefficients
    if cfg.reweight == "deterministic":
        alpha = det_alpha
    else:
        alpha = reweight.alpha_stochastic(new_counters, p_pos=1.0)

    trained = _constrain_buckets(spec, mesh, flatten_stacked(spec, trained_tree),
                                 stacked=True)

    progress = (None,) * spec.n_buckets
    progress_codes = (None,) * spec.n_buckets
    if cfg.quant_bits > 0 and quant_fused:
        # FAVAS[QNN], codes-in transport: LUQ-encode the transmitted
        # progress per BUCKET on the flat buffers (per-(row, shard) scales)
        # and keep it as packed codes all the way into the fused pass — the
        # dense (n, Dp) dequantized progress never materializes. Keys fold
        # off k_q under a dedicated tag so the stream can never collide
        # with the paged path's eviction fold (fold_in(k_q, 1)).
        progress_codes = _encode_progress(spec, trained, state.inits, k_q,
                                          cfg.quant_bits, mesh=mesh,
                                          use_kernel=use_kernel)
    elif cfg.quant_bits > 0:
        # FAVAS[QNN]: quantize the TRANSMITTED progress in tree space
        # (per-leaf LUQ scale, same per-leaf keys as the seed
        # implementation). Quantization is communication-only (Remark 1):
        # the fused pass aggregates Q(progress) but resets unselected
        # clients to their full-precision trained state.
        inits_tree = unflatten_stacked(spec, state.inits)
        prog = quantize_tree(tree_map(jnp.subtract, trained_tree, inits_tree),
                             cfg.quant_bits, k_q)
        progress = _constrain_buckets(spec, mesh, flatten_stacked(spec, prog),
                                      stacked=True)

    # 4+5. aggregation + selected-client reset: one pass per bucket.
    # alpha/mask ride to the kernel padded alongside the buffers' client
    # rows (unit alpha / zero mask => padded rows aggregate exactly nothing
    # and reset to themselves, i.e. stay zero). sample_selection_indices is
    # the SAME rng stream as sample_selection (the mask is derived from the
    # indices), so taking the indices here changes no draw.
    sel_idx, m = sampler.sample_selection_indices(k_sel, n, s)  # (s,), (n,)
    alpha_p = pad_client_vec(spec, alpha, 1.0)
    m_p = pad_client_vec(spec, m, 0.0)
    server_new, clients_new, inits_new = [], [], []
    if schedule == "streamed":
        # §13: single-sweep aggregation, then ONE churn-bounded scatter of
        # the new server row into the s selected rows of the donated
        # trained/init buffers — unselected rows are never rewritten
        for b in range(spec.n_buckets):
            server_new.append(stream_bucket_update(
                spec, b, state.server[b], trained[b], state.inits[b],
                alpha_p, m_p, float(s), progress_b=progress[b],
                progress_codes_b=progress_codes[b],
                progress_bits=cfg.quant_bits, n_logical=n, mesh=mesh,
                use_kernel=use_kernel))
        clients_new = _streamed_reset(spec, mesh, trained, sel_idx,
                                      server_new)
        inits_new = _streamed_reset(spec, mesh, state.inits, sel_idx,
                                    server_new)
    else:
        for b in range(spec.n_buckets):
            srv, cli, ini = fused_bucket_update(
                spec, b, state.server[b], trained[b], state.inits[b],
                alpha_p, m_p, float(s), progress_b=progress[b],
                progress_codes_b=progress_codes[b],
                progress_bits=cfg.quant_bits, n_logical=n, mesh=mesh,
                use_kernel=use_kernel)
            server_new.append(srv)
            clients_new.append(cli)
            inits_new.append(ini)

    counters_new = jnp.where(m > 0, 0, new_counters).astype(jnp.int32)
    stale_new = jnp.where(m > 0, 0, state.stale + 1).astype(jnp.int32)

    new_state = EngineState(server=tuple(server_new),
                            clients=tuple(clients_new),
                            inits=tuple(inits_new),
                            counters=counters_new, stale=stale_new,
                            key=key, t=state.t + 1)
    total_live = jnp.sum(live)
    metrics = {
        # live-step-weighted: clients that ran zero live steps this round
        # contribute nothing instead of dragging the mean toward 0, and a
        # stale straggler's high loss is weighted by its actual step count.
        "loss": jnp.sum(loss_sum) / jnp.maximum(total_live, 1.0),
        "mean_steps": jnp.mean(new_counters.astype(jnp.float32)),
        "selected": jnp.sum(m),
        "stale_rounds": jnp.max(stale_new).astype(jnp.float32),
    }
    return new_state, metrics


def _paged_round(spec: FlatSpec, state: EngineState, batch, *, cfg,
                 loss_fn: Callable, lambdas,
                 det_alpha: Optional[jnp.ndarray] = None,
                 use_kernel: Optional[bool] = None, mesh=None,
                 quant_fused: bool = False, corpus=None, batch_key=None,
                 schedule: str = "streamed", slab=None, plan=None):
    """One FAVAS round on a paged (hot/cold) spec — docs/architecture.md §9.

    Control flow inverts relative to the dense body: Gumbel top-s selection
    runs FIRST, then the hot working set is rebuilt (promote selected cold
    clients by gather+dequant, evict the stalest hot rows by requant+
    scatter-back), and only the ``s_max`` hot rows see local SGD and the
    fused aggregation+reset. Cold clients are frozen — their parameters,
    counters and progress do not move until promotion, which is exactly the
    dense semantics for never-selected clients once ``s_max`` covers every
    client touched between two selections of any given id.

    RNG streams: the round draws ``key, k_inc, k_sel, k_q`` from the SAME
    four-way split as the dense body — selection's key is merely consumed
    earlier — and all codec randomness is folded off ``k_q``, never split
    from the chain. With the passthrough codec at ``s_max == n`` (hot stacks
    = all clients in id order, identical shapes, identical reduction trees)
    the round is therefore bit-exact with the dense ``engine_round``.

    Host-placed cold tier (``spec.cold_placement == 'host'``, docs §13):
    ``state.cold`` is None inside the trace — the full cold pools live in
    host memory (:class:`repro.core.streaming.HostColdPool`) and the round
    reads/writes a device-resident SLAB holding one encoded row per client
    that churns anywhere in the current chunk. ``plan`` carries this
    round's ``{"evict_slab", "promo_slab"}`` (s_churn,) slab positions
    (precomputed by :func:`plan_rounds` + ``streaming.build_chunk_plan``
    from the bookkeeping-only replay of the key chain; invalid churn slots
    point at the all-zero dummy row), and the round returns ``(state, slab,
    metrics)`` so the slab rides the superstep carry. Because each churning
    id owns exactly one slab row, an evict at round t is visible to that
    id's promotion at any later round of the chunk — the same read-after-
    write order the device pools give for free."""
    n, s, K = cfg.n_clients, cfg.s_selected, cfg.local_steps
    s_hot = spec.s_max
    codec = spec.cold_codec
    host_cold = spec.cold_placement == "host"
    if host_cold and (slab is None or plan is None):
        raise ValueError("cold_placement='host' rounds need the slab and "
                         "per-round plan (see RoundEngine/engine_run_stream)")
    if not host_cold and (slab is not None or plan is not None):
        raise ValueError("slab/plan only apply to cold_placement='host'")
    key, k_inc, k_sel, k_q = jax.random.split(state.key, 4)

    # 1. heterogeneous progress + SELECT-FIRST
    d = sampler.sample_increments(k_inc, lambdas)               # (n,)
    _, m = sampler.sample_selection_indices(k_sel, n, s)        # (n,) 0/1
    stale_new = jnp.where(m > 0, 0, state.stale + 1).astype(jnp.int32)

    # 2. new hot membership: the s_max most recently selected clients.
    # Two-key lexsort (staleness, then id) instead of a composite score —
    # stale * n + id overflows int32 at populations this layer targets.
    # Membership stays ascending by id, so s_max == n degenerates to
    # arange(n), the dense row layout. Selected clients (staleness 0)
    # always fit: engine_init enforces s <= s_max.
    order = jnp.lexsort((jnp.arange(n, dtype=jnp.int32), stale_new))
    members = jnp.sort(order[:s_hot]).astype(jnp.int32)
    old_ids = state.hot_ids
    pos_in_old = jnp.clip(jnp.searchsorted(old_ids, members), 0, s_hot - 1)
    was_hot = old_ids[pos_in_old] == members                    # (s_max,)
    pos_in_new = jnp.clip(jnp.searchsorted(members, old_ids), 0, s_hot - 1)
    evicted = members[pos_in_new] != old_ids                    # (s_max,)

    # 3. evict: requant the rows leaving the hot set into the cold pools.
    # Membership churn is bounded by s_selected — only a client selected
    # THIS round can enter the hot set (staleness order among unselected
    # clients is preserved round to round), and the hot set has fixed size,
    # so at most s rows leave and at most s rows are promoted. The codec
    # therefore touches s_churn = min(s, s_max) rows, not the whole working
    # set. nonzero() pads the churn index vectors with out-of-range
    # positions; pad entries are routed to a row that is NOT churning this
    # round and write back its current value, so duplicate scatter indices
    # always carry identical values — deterministic, and a bit-exact no-op
    # in the s_max == n parity regime where nothing ever churns.
    s_churn = min(s, s_hot)

    def _churn_positions(flags):
        pos = jnp.nonzero(flags, size=s_churn, fill_value=s_hot)[0]
        valid = pos < s_hot
        safe = jnp.argmin(flags).astype(pos.dtype)  # first non-churning row
        return jnp.where(valid, jnp.minimum(pos, s_hot - 1), safe), valid

    evict_pos, evict_valid = _churn_positions(evicted)
    promo_pos, promo_valid = _churn_positions(~was_hot)

    # Unique sorted scatter ids + donation => in-place read-modify-write;
    # non-evicted clients' cold bytes are untouched. The encode key is
    # FOLDED off k_q (not split), leaving the dense key chain intact.
    k_evict = jax.random.fold_in(k_q, 1)
    evict_ids = old_ids[evict_pos]
    # host tier: churn ids become slab rows; invalid slots hit the all-zero
    # dummy row and write back its own gathered value (a no-op). The id
    # spaces differ but the ENCODED BYTES are identical — the codec key
    # chain never branches on placement.
    evict_rows = plan["evict_slab"] if host_cold else evict_ids
    pools = slab if host_cold else state.cold
    cold = []
    for b in range(spec.n_buckets):
        enc = codec.encode_pair(
            state.clients[b][evict_pos], state.inits[b][evict_pos],
            jax.random.fold_in(k_evict, b), shards=spec.shards(b),
            use_kernel=use_kernel)

        def scatter(pool, e):
            sel = evict_valid.reshape((-1,) + (1,) * (e.ndim - 1))
            return pool.at[evict_rows].set(
                jnp.where(sel, e.astype(pool.dtype), pool[evict_rows]))

        cold.append(jax.tree_util.tree_map(scatter, pools[b], enc))
    cold = _constrain_cold(spec, mesh, cold)

    # 4. promote: gather + dequant ONLY the rows entering the hot set. Rows
    # that never went cold keep their full-precision buffers — surviving
    # hot clients pay NO requant round-trip.
    rpad = spec.stacked_rows - s_hot
    promo_ids = members[promo_pos]
    promo_rows = plan["promo_slab"] if host_cold else promo_ids
    clients_hot, inits_hot = [], []
    for b in range(spec.n_buckets):
        dt = jnp.dtype(spec.bucket_dtypes[b])
        enc_rows = jax.tree_util.tree_map(lambda p: p[promo_rows], cold[b])
        dec_cli, dec_ini = codec.decode_pair(enc_rows, dt,
                                             shards=spec.shards(b),
                                             use_kernel=use_kernel)
        base_cli = state.clients[b][pos_in_old]
        base_ini = state.inits[b][pos_in_old]
        sel = promo_valid[:, None]
        cli = base_cli.at[promo_pos].set(
            jnp.where(sel, dec_cli, base_cli[promo_pos]))
        ini = base_ini.at[promo_pos].set(
            jnp.where(sel, dec_ini, base_ini[promo_pos]))
        if rpad:
            cli = jnp.pad(cli, ((0, rpad), (0, 0)))
            ini = jnp.pad(ini, ((0, rpad), (0, 0)))
        clients_hot.append(cli)
        inits_hot.append(ini)
    clients_hot = _constrain_buckets(spec, mesh, clients_hot, stacked=True)
    inits_hot = _constrain_buckets(spec, mesh, inits_hot, stacked=True)

    # 5. hot-set bookkeeping + batch rows (the credit clock advances for
    # hot clients only — cold clients are frozen, not merely unselected)
    q0 = state.counters[members]
    q1 = jnp.minimum(q0 + d[members], K)
    m_hot = m[members]
    if corpus is not None:
        batch = corpus.sample_round_batch(batch_key, cfg.R, ids=members)
    else:
        batch = tree_map(lambda x: x[members], batch)

    # 6. masked local SGD over the hot rows only
    clients_tree = unflatten_stacked(spec, clients_hot)
    trained_tree, loss_sum, live = _local_training(
        loss_fn, cfg, clients_tree, q0, q1, batch)

    # 7. eq. (3) coefficients + optional FAVAS[QNN] transmitted progress,
    # all in hot space (at s_max == n these are the dense expressions,
    # k_q included)
    if cfg.reweight == "deterministic":
        alpha = det_alpha[members]
    else:
        alpha = reweight.alpha_stochastic(q1, p_pos=1.0)
    trained = _constrain_buckets(spec, mesh,
                                 flatten_stacked(spec, trained_tree),
                                 stacked=True)
    progress = (None,) * spec.n_buckets
    progress_codes = (None,) * spec.n_buckets
    if cfg.quant_bits > 0 and quant_fused:
        # codes-in transport over the HOT stacks (see engine_round): the
        # 0x7166 tag keeps the fold stream disjoint from k_evict above
        progress_codes = _encode_progress(spec, trained, inits_hot, k_q,
                                          cfg.quant_bits, mesh=mesh,
                                          use_kernel=use_kernel)
    elif cfg.quant_bits > 0:
        inits_tree = unflatten_stacked(spec, inits_hot)
        prog = quantize_tree(tree_map(jnp.subtract, trained_tree, inits_tree),
                             cfg.quant_bits, k_q)
        progress = _constrain_buckets(spec, mesh, flatten_stacked(spec, prog),
                                      stacked=True)

    # 8. aggregation + selected-client reset over the hot stacks
    alpha_p = pad_client_vec(spec, alpha, 1.0)
    m_p = pad_client_vec(spec, m_hot, 0.0)
    server_new, clients_new, inits_new = [], [], []
    if schedule == "streamed":
        # §13: every selected client is hot (engine_init enforces
        # s <= s_max), so m_hot carries exactly s ones and the nonzero
        # fill value is never consumed. Scatter replaces the second sweep.
        sel_pos = jnp.nonzero(m_hot > 0, size=s, fill_value=0)[0]
        for b in range(spec.n_buckets):
            server_new.append(stream_bucket_update(
                spec, b, state.server[b], trained[b], inits_hot[b],
                alpha_p, m_p, float(s), progress_b=progress[b],
                progress_codes_b=progress_codes[b],
                progress_bits=cfg.quant_bits, n_logical=s_hot, mesh=mesh,
                use_kernel=use_kernel))
        clients_new = _streamed_reset(spec, mesh, trained, sel_pos,
                                      server_new)
        inits_new = _streamed_reset(spec, mesh, inits_hot, sel_pos,
                                    server_new)
    else:
        for b in range(spec.n_buckets):
            srv, cli, ini = fused_bucket_update(
                spec, b, state.server[b], trained[b], inits_hot[b], alpha_p,
                m_p, float(s), progress_b=progress[b],
                progress_codes_b=progress_codes[b],
                progress_bits=cfg.quant_bits, n_logical=s_hot,
                mesh=mesh, use_kernel=use_kernel)
            server_new.append(srv)
            clients_new.append(cli)
            inits_new.append(ini)

    # 9. scatter the hot counter updates back into the full-n view
    counters_new = state.counters.at[members].set(
        jnp.where(m_hot > 0, 0, q1).astype(jnp.int32))

    new_state = EngineState(server=tuple(server_new),
                            clients=tuple(clients_new),
                            inits=tuple(inits_new),
                            counters=counters_new, stale=stale_new,
                            key=key, t=state.t + 1,
                            hot_ids=members,
                            cold=None if host_cold else cold)
    total_live = jnp.sum(live)
    metrics = {
        # live-step-weighted over the SELECTED HOT SET only: frozen cold
        # clients run zero live steps and contribute nothing — paging must
        # not reintroduce the zero-live-step masking bug (ROADMAP notes;
        # regression-pinned in tests/test_paged_engine.py)
        "loss": jnp.sum(loss_sum) / jnp.maximum(total_live, 1.0),
        "mean_steps": jnp.mean(q1.astype(jnp.float32)),
        "selected": jnp.sum(m),
        "stale_rounds": jnp.max(stale_new).astype(jnp.float32),
    }
    if host_cold:
        return new_state, tuple(cold), metrics
    return new_state, metrics


def plan_rounds(spec: FlatSpec, cfg, key, stale, hot_ids, *,
                n_rounds: int, device_plane: bool = False):
    """Bookkeeping-only replay of ``n_rounds`` of the paged key chain — the
    host-tier planner (docs §13). Hot-set membership depends only on
    ``(key, stale, hot_ids)``: selection and the staleness lexsort never
    read parameters, so the chunk's churn schedule is known BEFORE the
    chunk runs — that is what lets the page streamer fetch the next
    chunk's cold rows while this chunk computes. Returns ``(carry, plan)``:
    ``carry = (key, stale, hot_ids)`` is the bookkeeping AFTER the chunk
    (feed it back in to plan the next chunk ahead of time) and ``plan`` is
    the stacked ``(n_rounds, s_churn)`` arrays ``{"evict_ids",
    "evict_valid", "promo_ids", "promo_valid"}``; invalid churn slots
    carry id 0 with valid=False (``streaming.build_chunk_plan`` routes
    them to the slab's dummy row).

    The replay draws the SAME splits as :func:`_paged_round` — ``k_inc``
    and ``k_q`` are consumed but unused (a split is key arithmetic, not
    state mutation, so skipping the unused streams changes nothing), and
    ``device_plane=True`` burns the per-round batch key first, exactly
    like the device-plane scan body in :func:`engine_multi_round`."""
    n, s = cfg.n_clients, cfg.s_selected
    s_hot = spec.s_max
    s_churn = min(s, s_hot)

    def body(carry, _):
        key, stale, old_ids = carry
        if device_plane:
            key, _kb = jax.random.split(key)
        key, _k_inc, k_sel, _k_q = jax.random.split(key, 4)
        _, m = sampler.sample_selection_indices(k_sel, n, s)
        stale_new = jnp.where(m > 0, 0, stale + 1).astype(jnp.int32)
        order = jnp.lexsort((jnp.arange(n, dtype=jnp.int32), stale_new))
        members = jnp.sort(order[:s_hot]).astype(jnp.int32)
        pos_in_old = jnp.clip(jnp.searchsorted(old_ids, members),
                              0, s_hot - 1)
        was_hot = old_ids[pos_in_old] == members
        pos_in_new = jnp.clip(jnp.searchsorted(members, old_ids),
                              0, s_hot - 1)
        evicted = members[pos_in_new] != old_ids

        def _churn(flags, ids):
            pos = jnp.nonzero(flags, size=s_churn, fill_value=s_hot)[0]
            valid = pos < s_hot
            safe = jnp.argmin(flags).astype(pos.dtype)
            pos = jnp.where(valid, jnp.minimum(pos, s_hot - 1), safe)
            return jnp.where(valid, ids[pos], 0).astype(jnp.int32), valid

        evict_ids, evict_valid = _churn(evicted, old_ids)
        promo_ids, promo_valid = _churn(~was_hot, members)
        out = {"evict_ids": evict_ids, "evict_valid": evict_valid,
               "promo_ids": promo_ids, "promo_valid": promo_valid}
        return (key, stale_new, members), out

    return jax.lax.scan(body, (key, stale, hot_ids), None, length=n_rounds)


def engine_multi_round(spec: FlatSpec, state: EngineState, batches=None, *,
                       cfg, loss_fn: Callable, lambdas,
                       det_alpha: Optional[jnp.ndarray] = None,
                       use_kernel: Optional[bool] = None, mesh=None,
                       quant_fused: bool = False,
                       corpus=None, n_rounds: Optional[int] = None,
                       schedule: str = "streamed",
                       slab=None, plans=None):
    """A whole chunk of FAVAS rounds as ONE ``jax.lax.scan`` — the
    "superstep" (docs/architecture.md §7). Pure; jit/pjit this and donate
    ``state``: a T-round chunk then costs one dispatch instead of T.

    Two data planes feed the scan (docs/architecture.md §8):

    * **host plane** — ``batches`` is the per-round batch pytree with an
      extra LEADING rounds axis — leaves are (T, n, R, ...); round t
      consumes slice ``batches[t]``;
    * **device plane** — ``corpus`` is a
      :class:`repro.data.device_corpus.DeviceCorpus` and ``n_rounds`` the
      (static) chunk length: the scan body draws each round's per-client
      minibatch indices from the carried PRNG key and gathers the rows on
      device (``corpus.sample_round_batch``), so a compiled chunk does ZERO
      host batch-generation work between dispatches.

    The scan carries the :class:`EngineState` and stacks each round's
    metrics, so the caller fetches one (T,)-shaped metrics pytree per chunk
    instead of blocking on T scalar transfers.

    RNG equivalence: :func:`engine_round` derives everything it draws from
    ``state.key`` (split once per round, the new key rides in the carry), so
    the scanned host-plane stream is IDENTICAL to T sequential
    ``engine_round`` calls — superstep-vs-sequential parity is bit-exact,
    not approximate (tests/test_superstep.py). The device plane splits one
    extra batch key per round off the same chain (see
    tests/test_device_corpus.py for the sequential-parity proof), so it is
    *statistically equivalent* to the host plane, not stream-identical —
    the same contract PR 4 set for on-device selection. Composes with
    ``use_kernel`` and ``mesh`` exactly like ``engine_round``: the
    shard_map / pjit dispatch sits inside the scan body, compiled once for
    the whole chunk.

    Host-placed cold tier (``slab``/``plans`` not None, docs §13): the scan
    carries ``(state, slab)`` and consumes the per-round plan xs, and the
    call returns ``(new_state, new_slab, metrics)`` — the caller (the
    :class:`RoundEngine` host prologue or ``streaming.engine_run_stream``)
    owns the gather/writeback against the host pool around the dispatch.

    Returns ``(new_state, metrics)`` with every metric stacked to (T,)."""
    host_cold = slab is not None
    if host_cold and plans is None:
        raise ValueError("a host-tier superstep needs the per-round plans "
                         "(see plan_rounds / streaming.build_chunk_plan)")
    if corpus is not None:
        if batches is not None:
            raise ValueError("pass either batches (host plane) or corpus "
                             "(device plane), not both")
        if n_rounds is None:
            raise ValueError("the device plane needs a static n_rounds "
                             "(there is no batches axis to infer it from)")

        def body_c(st, plan):
            key, k_batch = jax.random.split(st[0].key if host_cold else st.key)
            # sampling happens INSIDE engine_round (same key, same draws as
            # sampling here): a paged spec must select its hot working set
            # before it knows which corpus rows to gather
            if host_cold:
                st0 = dataclasses.replace(st[0], key=key)
                st1, sl, met = engine_round(
                    spec, st0, None, cfg=cfg, loss_fn=loss_fn,
                    lambdas=lambdas, det_alpha=det_alpha,
                    use_kernel=use_kernel, mesh=mesh,
                    quant_fused=quant_fused, corpus=corpus,
                    batch_key=k_batch, schedule=schedule,
                    slab=st[1], plan=plan)
                return (st1, sl), met
            st = dataclasses.replace(st, key=key)
            return engine_round(spec, st, None, cfg=cfg, loss_fn=loss_fn,
                                lambdas=lambdas, det_alpha=det_alpha,
                                use_kernel=use_kernel, mesh=mesh,
                                quant_fused=quant_fused,
                                corpus=corpus, batch_key=k_batch,
                                schedule=schedule)
        if host_cold:
            (st1, sl1), metrics = jax.lax.scan(body_c, (state, slab), plans,
                                               length=n_rounds)
            return st1, sl1, metrics
        return jax.lax.scan(body_c, state, None, length=n_rounds)

    if host_cold:
        def body_h(carry, xs):
            batch, plan = xs
            st1, sl, met = engine_round(spec, carry[0], batch, cfg=cfg,
                                        loss_fn=loss_fn, lambdas=lambdas,
                                        det_alpha=det_alpha,
                                        use_kernel=use_kernel, mesh=mesh,
                                        quant_fused=quant_fused,
                                        schedule=schedule,
                                        slab=carry[1], plan=plan)
            return (st1, sl), met
        (st1, sl1), metrics = jax.lax.scan(body_h, (state, slab),
                                           (batches, plans))
        return st1, sl1, metrics

    def body(st, batch):
        return engine_round(spec, st, batch, cfg=cfg, loss_fn=loss_fn,
                            lambdas=lambdas, det_alpha=det_alpha,
                            use_kernel=use_kernel, mesh=mesh,
                            quant_fused=quant_fused, schedule=schedule)
    return jax.lax.scan(body, state, batches)


def engine_server_params(spec: FlatSpec, state: EngineState):
    """Current server model as the original parameter pytree."""
    return unflatten_tree(spec, state.server)


def engine_variance(state: EngineState) -> jnp.ndarray:
    """sum_i ||w^i - w_t||^2 straight off the flat buffers. Padded lane
    tails are identical between clients and server (zero contribution);
    padded client ROWS are all-zero, not copies of the server, so they are
    sliced off (the counters carry the logical n).

    On a paged state the sum runs over the HOT WORKING SET only — the rows
    that actually trained. Decoding the cold pool here would charge frozen
    clients' (possibly quantized) drift to a live-progress metric and
    reintroduce the zero-live-step averaging bug at the variance level; at
    ``s_max == n`` the hot set is everyone and this is the dense value."""
    rows = (state.counters.shape[0] if state.hot_ids is None
            else state.hot_ids.shape[0])
    tot = jnp.zeros((), jnp.float32)
    for srv, cli in zip(state.server, state.clients):
        diff = cli[:rows].astype(jnp.float32) - srv[None].astype(jnp.float32)
        tot = tot + jnp.sum(jnp.square(diff))
    return tot


def engine_resident_bytes_by_tier(state: EngineState) -> dict:
    """Per-memory-tier byte accounting of the engine state — what the
    residency benches and the CI resident-bytes gates measure. Host-side
    accounting; not jittable.

    ``device``: hot stacks + server + bookkeeping + (device-placed) cold
    pools — everything that occupies accelerator HBM. ``host``: the
    :class:`repro.core.streaming.HostColdPool` pools of a host-placed cold
    tier (zero otherwise). Host pools must NEVER count against the device
    budget — moving them off-device is the whole point of ``cold_placement
    ='host'`` (docs §13); ``benchmarks.paged_state_bench`` asserts both
    tiers against the live arrays."""
    from repro.core.streaming import HostColdPool   # lazy: no import cycle
    device = host = 0
    leaves = jax.tree_util.tree_leaves(
        state, is_leaf=lambda x: isinstance(x, HostColdPool))
    for leaf in leaves:
        if isinstance(leaf, HostColdPool):
            host += leaf.nbytes
        else:
            device += leaf.size * jnp.dtype(leaf.dtype).itemsize
    return {"device": device, "host": host}


def engine_resident_bytes(state: EngineState) -> int:
    """DEVICE-tier bytes of the state (hot stacks + device-placed cold
    pools + bookkeeping) — see :func:`engine_resident_bytes_by_tier`. For
    device-placed specs this is every array in the state, the historical
    meaning; host-placed cold pools are excluded by construction."""
    return engine_resident_bytes_by_tier(state)["device"]


# ---------------------------------------------------------------------------
# RoundEngine: holds the static spec + a donated jitted round
# ---------------------------------------------------------------------------

class RoundEngine:
    """Convenience wrapper owning the FlatSpec and the jitted, buffer-donating
    round. The state never leaves flat form between rounds.

    ``mesh``: run the engine mesh-native — the spec buckets leaves by
    (dtype, sharding group), ``init_state`` places the buffers with
    :func:`engine_sharding`, and every round keeps sharded buckets on the
    model axis end-to-end (``--mesh`` in ``launch.train`` composes this with
    ``--use-kernel``: kernel -> shard_map per shard, oracle -> pjit)."""

    def __init__(self, params_template, cfg, loss_fn: Callable, *,
                 lambdas=None, det_alpha=None, use_kernel: Optional[bool] = None,
                 client_tile: int = CLIENT_TILE, mesh=None,
                 residency: str = "dense", s_max: Optional[int] = None,
                 cold_bits: int = 0, quant_fused: bool = False,
                 cold_placement: str = "device",
                 schedule: str = "streamed"):
        from repro.core.favas import client_lambdas  # cycle-free at call time
        self.cfg = cfg
        self.mesh = mesh
        codec = make_codec(cold_bits) if residency == "paged" else None
        self.spec = make_flat_spec(params_template, n_clients=cfg.n_clients,
                                   client_tile=client_tile, mesh=mesh,
                                   residency=residency, s_max=s_max,
                                   cold_codec=codec,
                                   cold_placement=cold_placement)
        self.loss_fn = loss_fn
        self.lambdas = (jnp.asarray(lambdas) if lambdas is not None
                        else jnp.asarray(client_lambdas(cfg)))
        self.det_alpha = None if det_alpha is None else jnp.asarray(det_alpha)
        self.use_kernel = use_kernel
        self.quant_fused = quant_fused
        self.schedule = schedule
        self._round = jax.jit(
            functools.partial(engine_round, self.spec, cfg=self.cfg,
                              loss_fn=self.loss_fn, lambdas=self.lambdas,
                              det_alpha=self.det_alpha,
                              use_kernel=self.use_kernel, mesh=self.mesh,
                              quant_fused=self.quant_fused,
                              schedule=self.schedule),
            donate_argnums=(0,))
        self._multi = jax.jit(
            functools.partial(engine_multi_round, self.spec, cfg=self.cfg,
                              loss_fn=self.loss_fn, lambdas=self.lambdas,
                              det_alpha=self.det_alpha,
                              use_kernel=self.use_kernel, mesh=self.mesh,
                              quant_fused=self.quant_fused,
                              schedule=self.schedule),
            donate_argnums=(0,))
        # device data plane: the corpus rides as a pytree ARGUMENT (not a
        # closure) so its buffers are shared inputs, never baked into the
        # executable as constants; n_rounds is static (scan length)
        self._multi_device = jax.jit(
            functools.partial(engine_multi_round, self.spec, cfg=self.cfg,
                              loss_fn=self.loss_fn, lambdas=self.lambdas,
                              det_alpha=self.det_alpha,
                              use_kernel=self.use_kernel, mesh=self.mesh,
                              quant_fused=self.quant_fused,
                              schedule=self.schedule),
            static_argnames=("n_rounds",), donate_argnums=(0,))
        # host-placed cold tier (docs §13): the slab rides positionally so
        # it can be donated alongside the state; state.cold is None inside
        # every trace — the HostColdPool never crosses into jit
        if self.spec.paged and self.spec.cold_placement == "host":
            common = dict(cfg=self.cfg, loss_fn=self.loss_fn,
                          lambdas=self.lambdas, det_alpha=self.det_alpha,
                          use_kernel=self.use_kernel, mesh=self.mesh,
                          quant_fused=self.quant_fused,
                          schedule=self.schedule)
            spec = self.spec

            def _rh(state, batch, slab, plan):
                return engine_round(spec, state, batch, slab=slab,
                                    plan=plan, **common)

            def _mh(state, slab, batches, plans):
                return engine_multi_round(spec, state, batches, slab=slab,
                                          plans=plans, **common)

            def _mdh(state, slab, plans, corpus, n_rounds):
                return engine_multi_round(spec, state, corpus=corpus,
                                          n_rounds=n_rounds, slab=slab,
                                          plans=plans, **common)

            self._round_host = jax.jit(_rh, donate_argnums=(0, 2))
            self._multi_host = jax.jit(_mh, donate_argnums=(0, 1))
            self._multi_device_host = jax.jit(
                _mdh, static_argnames=("n_rounds",), donate_argnums=(0, 1))
            self._plan = jax.jit(
                functools.partial(plan_rounds, self.spec, self.cfg),
                static_argnames=("n_rounds", "device_plane"))
        # dispatches into the jitted round/superstep — the regression guard
        # tests/test_superstep.py uses to pin "one chunk = one dispatch"
        self.dispatch_count = 0

    def init_state(self, params, key) -> EngineState:
        state = engine_init(self.spec, params, self.cfg, key,
                            use_kernel=self.use_kernel)
        if self.mesh is not None:
            # a host-placed cold pool is numpy, not a device array — it
            # must not ride through device_put (engine_sharding's tree has
            # cold=None for host placement, matching the stripped state)
            pool = state.cold if self.spec.cold_placement == "host" else None
            if pool is not None:
                state = dataclasses.replace(state, cold=None)
            state = jax.device_put(state, engine_sharding(self.spec, self.mesh))
            if pool is not None:
                state = dataclasses.replace(state, cold=pool)
        return state

    # -- host-placed cold tier: gather/writeback around each dispatch -----
    def _host_prologue(self, state: EngineState, n_rounds: int,
                       device_plane: bool):
        """Plan the chunk's churn, gather its slab from the host pool, and
        move both to device. Returns ``(state_sans_pool, pool, uids, slab,
        plans)`` — see docs §13 and :mod:`repro.core.streaming`."""
        from repro.core import streaming
        pool = state.cold
        state = dataclasses.replace(state, cold=None)
        _, plan = self._plan(state.key, state.stale, state.hot_ids,
                             n_rounds=n_rounds, device_plane=device_plane)
        plan = jax.device_get(plan)
        slab_rows = streaming.chunk_slab_rows(self.spec, self.cfg, n_rounds)
        uids, slab_plan = streaming.build_chunk_plan(plan,
                                                     slab_rows=slab_rows)
        slab_np = pool.gather(uids, slab_rows)
        shardings = slab_shardings(self.spec, self.mesh)
        slab = (jax.device_put(slab_np, shardings) if shardings is not None
                else jax.device_put(slab_np))
        plans = jax.tree_util.tree_map(jnp.asarray, slab_plan)
        return state, pool, uids, slab, plans

    def _host_epilogue(self, state: EngineState, pool, uids, slab):
        """Write the chunk's final slab rows back into the host pool and
        re-attach it to the state."""
        pool.writeback(uids, jax.device_get(slab))
        return dataclasses.replace(state, cold=pool)

    def step(self, state: EngineState, batch):
        """Jitted round; donates the previous state's buffers."""
        self.dispatch_count += 1
        if self.spec.paged and self.spec.cold_placement == "host":
            state, pool, uids, slab, plans = self._host_prologue(
                state, 1, device_plane=False)
            plan0 = jax.tree_util.tree_map(lambda x: x[0], plans)
            state, slab, metrics = self._round_host(state, batch, slab,
                                                    plan0)
            return self._host_epilogue(state, pool, uids, slab), metrics
        return self._round(state, batch)

    def run(self, state: EngineState, batches,
            n_rounds: Optional[int] = None):
        """A chunk of rounds as one superstep dispatch (see
        :func:`engine_multi_round`); donates the previous state's buffers.

        ``batches``: per-round batch pytree with a leading (T,) rounds axis.
        ``n_rounds``: optional sanity check that T is what the caller thinks
        it is (chunks of different T compile once each — the scan length is
        static). Returns ``(new_state, metrics)`` with (T,)-stacked metrics;
        bit-exact with T sequential :meth:`step` calls."""
        T = jax.tree_util.tree_leaves(batches)[0].shape[0]
        if n_rounds is not None and n_rounds != T:
            raise ValueError(
                f"batches carry {T} rounds but n_rounds={n_rounds}")
        self.dispatch_count += 1
        if self.spec.paged and self.spec.cold_placement == "host":
            state, pool, uids, slab, plans = self._host_prologue(
                state, T, device_plane=False)
            state, slab, metrics = self._multi_host(state, slab, batches,
                                                    plans)
            return self._host_epilogue(state, pool, uids, slab), metrics
        return self._multi(state, batches)

    def run_device(self, state: EngineState, corpus, n_rounds: int):
        """A chunk of rounds on the DEVICE data plane: one superstep
        dispatch whose scan body samples each round's minibatches from the
        resident ``corpus`` (a ``data.device_corpus.DeviceCorpus``) — no
        host batch generation, no H2D batch traffic, no prefetcher.
        Donates the previous state's buffers; ``n_rounds`` is static (one
        compilation per distinct chunk length, like the host plane's batch
        shapes). Returns ``(new_state, metrics)`` with (T,)-stacked
        metrics."""
        self.dispatch_count += 1
        if self.spec.paged and self.spec.cold_placement == "host":
            state, pool, uids, slab, plans = self._host_prologue(
                state, n_rounds, device_plane=True)
            state, slab, metrics = self._multi_device_host(
                state, slab, plans, corpus, n_rounds=n_rounds)
            return self._host_epilogue(state, pool, uids, slab), metrics
        return self._multi_device(state, corpus=corpus, n_rounds=n_rounds)

    def server_params(self, state: EngineState):
        return engine_server_params(self.spec, state)

    def variance(self, state: EngineState) -> jnp.ndarray:
        return engine_variance(state)

    def resident_bytes(self, state: EngineState) -> int:
        return engine_resident_bytes(state)

    def resident_bytes_by_tier(self, state: EngineState) -> dict:
        return engine_resident_bytes_by_tier(state)
