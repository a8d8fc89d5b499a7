"""Row-sharded embedding tables over the device mesh.

Parity: the reference's sharded-embedding stack — pserver-row-sharded
distributed_lookup_table (operators/distributed_ops/distributed_lookup_table_op.cc,
split by row blocks across pservers) and the PSLib sparse pull/push
(framework/fleet/fleet_wrapper.h:76 PullSparseVarsSync, :97
PushDenseVarsAsync).

TPU-native design (SURVEY.md §2.9 "PSLib" row + §7 stage 8): instead of RPC
pull/push to parameter servers, the table lives row-block-sharded across an
ICI mesh axis; a lookup is a local gather of the rows this shard owns plus
one psum over the axis (the all-to-all the PS RPC becomes on ICI).  Gradients
flow through the same shard_map — each shard receives exactly its own rows'
gradient (the scatter-add lands locally; XLA keeps it sharded), so the
optimizer update is local per shard: the Downpour "server-side update"
without a server.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = [
    "shard_rows",
    "embedding_spec",
    "sharded_embedding_lookup",
    "init_sharded_table",
    "init_embedding_table",
    "table_fits",
    "enable_host_sparse_table",
    "host_sparse_table_enabled",
]


def embedding_spec(axis="dp"):
    """PartitionSpec for a row-sharded [V, D] table — delegated to the
    sharding authority (parallel/rules.py row_sharded_table_spec), the same
    layout definition the checkpoint re-sharder and HostPS row partition
    (rules.hostps_row_range) derive from."""
    from . import rules as shard_rules

    return shard_rules.row_sharded_table_spec(axis)


def shard_rows(vocab_size, n_shards):
    """Rows per shard for the block layout (shard i owns
    [i*rows, (i+1)*rows)); vocab must divide evenly — pad the table at
    construction (init_sharded_table does)."""
    if vocab_size % n_shards:
        raise ValueError(
            "vocab %d not divisible by %d shards; pad the table "
            "(init_sharded_table rounds up)" % (vocab_size, n_shards))
    return vocab_size // n_shards


# per-chip HBM for the capacity guard below (bytes); queried from the
# device, overridable via configure_hbm_budget.  The CPU backend reports no
# bytes_limit, so tests and CPU runs budget against the v5e's 16 GiB; an
# accelerator that reports none is an error, not a v5e.
_HBM_BYTES_PER_CHIP = None                    # None = query the device
_HBM_FALLBACK_BYTES = 16 * 1024 ** 3          # cpu platform only
_HBM_TABLE_FRACTION = 0.6                     # leave room for acts/moments


def configure_hbm_budget(bytes_per_chip, table_fraction=0.6):
    """Set the per-chip HBM budget the table-capacity guard checks against."""
    global _HBM_BYTES_PER_CHIP, _HBM_TABLE_FRACTION
    _HBM_BYTES_PER_CHIP = int(bytes_per_chip)
    _HBM_TABLE_FRACTION = float(table_fraction)


def _hbm_bytes_per_chip():
    if _HBM_BYTES_PER_CHIP is not None:
        return _HBM_BYTES_PER_CHIP
    # the SHARED MemScope capacity helper: the tightest bytes_limit across
    # ALL local devices (a devices()[0]-only read would overbudget a host
    # whose chips differ), honoring the same configured override the
    # headroom predictor / admission math uses — router and admission
    # agree on one number by construction
    from ..monitor import memscope

    limit = memscope.min_device_bytes_limit()
    if limit:
        return int(limit)
    platform = jax.devices()[0].platform
    if platform != "cpu":
        raise RuntimeError(
            "%s devices report no memory_stats()['bytes_limit']; set the "
            "per-chip budget with configure_hbm_budget()" % platform)
    return _HBM_FALLBACK_BYTES


# routing flag: set by DistributedStrategy.use_host_sparse_table
# (distributed/fleet.py) or directly; when on, init_embedding_table routes
# beyond-budget vocabularies to the host-RAM service instead of erroring
_HOST_SPARSE_TABLE = False
_HOST_SPARSE_CACHE_SLOTS = 0   # default HotRowCache size for routed tables


def enable_host_sparse_table(on=True, cache_slots=None):
    """Route beyond-HBM-budget tables to paddle_tpu.hostps (the fleet
    strategy knob `use_host_sparse_table` calls this).  cache_slots, when
    given, becomes the default HBM hot-row cache size for tables the
    router sends to HostPS (strategy knob host_sparse_cache_slots)."""
    global _HOST_SPARSE_TABLE, _HOST_SPARSE_CACHE_SLOTS
    _HOST_SPARSE_TABLE = bool(on)
    if cache_slots is not None:
        _HOST_SPARSE_CACHE_SLOTS = int(cache_slots)


def host_sparse_table_enabled():
    return _HOST_SPARSE_TABLE


def table_fits(vocab_size, dim, n_shards=1, dtype=jnp.float32):
    """True when a [vocab, dim] table fits the mesh's aggregate HBM table
    budget (the init_embedding_table routing predicate)."""
    table_bytes = vocab_size * dim * jnp.dtype(dtype).itemsize
    per_chip = _hbm_bytes_per_chip()
    return table_bytes <= n_shards * per_chip * _HBM_TABLE_FRACTION


def _check_table_fits(vocab_size, dim, n_shards, dtype):
    """Mesh-sharded tables cap out at aggregate HBM — the reference's PSLib
    host-RAM sparse service (fleet_wrapper.h:55: tables too big for
    accelerator memory) exists exactly for what lies beyond, and its port
    here is paddle_tpu.hostps.  Past the limit, fail LOUDLY naming that
    route instead of letting the first allocation OOM cryptically."""
    if table_fits(vocab_size, dim, n_shards, dtype):
        return
    table_bytes = vocab_size * dim * jnp.dtype(dtype).itemsize
    per_chip = _hbm_bytes_per_chip()
    budget = n_shards * per_chip * _HBM_TABLE_FRACTION
    raise ValueError(
        "embedding table [%d x %d] (%s) needs %.1f GiB but the %d-shard "
        "mesh has only ~%.1f GiB of HBM budgeted for tables (%.0f%% of "
        "%d x %.0f GiB). Beyond-aggregate-HBM vocabularies are served by "
        "the host-RAM parameter-server port (paddle_tpu.hostps — the "
        "reference's PSLib/Downpour design): set "
        "DistributedStrategy.use_host_sparse_table = True "
        "(distributed/fleet.py) or call "
        "parallel.embedding.enable_host_sparse_table(), then build the "
        "table through init_embedding_table() to get a HostPSEmbedding "
        "handle. Otherwise shard over more chips, shrink dim, use a "
        "smaller dtype, or hash the vocabulary (layers.hash / pyramid-hash "
        "style bucketing). Budget is configurable via "
        "parallel.embedding.configure_hbm_budget()."
        % (vocab_size, dim, jnp.dtype(dtype).name,
           table_bytes / 1024 ** 3, n_shards, budget / 1024 ** 3,
           _HBM_TABLE_FRACTION * 100, n_shards,
           per_chip / 1024 ** 3))


def init_sharded_table(key, vocab_size, dim, n_shards, scale=None,
                       dtype=jnp.float32):
    """Init a [V_padded, D] table where V_padded rounds vocab up to a
    multiple of n_shards (the row-block split of the transpiler's
    slice_var_up, distribute_transpiler.py:131).  Raises a clear error when
    the table cannot fit the mesh's aggregate HBM (see _check_table_fits)."""
    pad = (-vocab_size) % n_shards
    v = vocab_size + pad
    _check_table_fits(v, dim, n_shards, dtype)
    scale = scale if scale is not None else 1.0 / jnp.sqrt(dim)
    # generate directly in the target dtype: an f32 staging copy would blow
    # the very budget _check_table_fits just validated for sub-f32 tables
    gen_dtype = dtype if jnp.issubdtype(dtype, jnp.floating) else jnp.float32
    t = jax.random.normal(key, (v, dim), gen_dtype) * jnp.asarray(
        scale, gen_dtype)
    return t.astype(dtype)


def init_embedding_table(key, vocab_size, dim, n_shards=1, scale=None,
                         dtype=jnp.float32, host_optimizer=None,
                         host_initializer=None, cache_slots=0, device=None,
                         name="embedding"):
    """Capacity ROUTER for sparse tables (the fleet_wrapper.h:55 decision
    point): a vocab that fits the mesh's aggregate HBM budget gets the
    in-HBM row-sharded [V, D] array (init_sharded_table); one that exceeds
    it routes to the host-RAM sparse service (paddle_tpu.hostps) when
    DistributedStrategy.use_host_sparse_table is set — returning a
    HostPSEmbedding pull/push handle — and raises the loud capacity error
    otherwise.

    host_optimizer/host_initializer/cache_slots apply only to the HostPS
    route: the server-side applier (hostps.optimizer), the
    init-on-first-pull row initializer (defaults to the same N(0, 1/sqrt(D))
    law as the in-HBM init), and the HBM hot-row cache size.
    """
    pad = (-vocab_size) % n_shards
    v = vocab_size + pad
    if table_fits(v, dim, n_shards, dtype):
        return init_sharded_table(key, vocab_size, dim, n_shards, scale=scale,
                                  dtype=dtype)
    if not host_sparse_table_enabled():
        _check_table_fits(v, dim, n_shards, dtype)   # raises, naming the knob
    from ..hostps import HostPSEmbedding, HostSparseTable
    from ..hostps.table import default_row_initializer

    np_dtype = jnp.dtype(dtype).name
    # derive the row-init seed from the PRNG key so the two routes share
    # one seeding surface (old-style keys are raw uint32 arrays)
    try:
        seed = int(np.asarray(jax.random.key_data(key)).ravel()[-1])
    except Exception:
        seed = int(np.asarray(key).ravel()[-1])
    init = host_initializer or default_row_initializer(
        dim, scale=scale, seed=seed, dtype=np_dtype)
    table = HostSparseTable(vocab_size, dim, optimizer=host_optimizer,
                            initializer=init, dtype=np_dtype, name=name)
    return HostPSEmbedding(table,
                           cache_slots=cache_slots or _HOST_SPARSE_CACHE_SLOTS,
                           device=device, name=name)


def sharded_embedding_lookup(table_shard, ids, axis_name):
    """Lookup on a row-block-sharded table, inside shard_map.

    table_shard: this shard's [V/n, D] row block.
    ids: REPLICATED [..,] int ids (full-vocab space).
    Returns the replicated gather result [.., D].

    One local gather + one psum: rows not owned contribute zeros.  Gradient
    caveat: psum's transpose is psum, so a loss computed redundantly per
    shard from this output must be wrapped in lax.pmean(loss, axis) (not a
    plain per-shard loss) for table cotangents to come out unscaled.  For
    batch-sharded ids use sharded_embedding_lookup_dp.
    """
    rows = table_shard.shape[0]
    lo = lax.axis_index(axis_name) * rows
    local = ids - lo
    own = (local >= 0) & (local < rows)
    safe = jnp.clip(local, 0, rows - 1)
    vals = jnp.where(own[..., None], table_shard[safe], 0)
    return lax.psum(vals, axis_name)


def sharded_embedding_lookup_dp(table_shard, ids_local, axis_name):
    """Row-sharded table × batch-sharded ids — the production CTR layout
    (each worker holds a batch shard AND a row block; the reference's
    per-trainer prefetch of remote rows, distributed_lookup_table_op.cc).

    all_gather the local ids over the axis, gather owned rows, psum, then
    slice this shard's batch back out.  The all_gather/psum pair is the ICI
    form of the PS pull; its transpose (scatter of grads to owner shards)
    is the push.
    """
    rows = table_shard.shape[0]
    me = lax.axis_index(axis_name)
    ids_all = lax.all_gather(ids_local, axis_name)   # [n, ...]
    local = ids_all - me * rows
    own = (local >= 0) & (local < rows)
    safe = jnp.clip(local, 0, rows - 1)
    vals = jnp.where(own[..., None], table_shard[safe], 0)
    # reduce_scatter: shard i receives the summed slot i — same result as
    # psum-then-slice at 1/n the interconnect payload
    return lax.psum_scatter(vals, axis_name, scatter_dimension=0, tiled=False)
