"""What one layer's causal grouped-query attention needs, full or windowed,
for the flash kernels' roofline (``swa_flash_roofline``)."""

from .smallthinker_train import seen_pairs


def required(batch, seq, n_heads, n_kv_heads, head_dim, window=None,
             itemsize=2):
    """FLOPs and HBM bytes of one layer's attention over ``batch``
    sequences, forward and backward apart.

    Forward: QK^T and PV, 4*dh FLOPs a (query, key) pair the mask lets
    through and a query head; reads q and writes o at ``n_heads`` heads,
    reads k and v at ``n_kv_heads``.  Backward: dV, dP, dQ, dK, 8*dh a pair
    and head (the recomputed QK^T does not count); reads q, o, do and writes
    dq at ``n_heads``, reads k, v and writes dk, dv at ``n_kv_heads``.  The
    f32 row statistics are under 1 % of the rest and left out."""
    pairs = batch * seen_pairs(seq, window) * n_heads * head_dim
    q_tile = batch * seq * n_heads * head_dim * itemsize
    kv_tile = batch * seq * n_kv_heads * head_dim * itemsize
    return {"fwd": {"flops": 4.0 * pairs,
                    "bytes": 2.0 * q_tile + 2.0 * kv_tile},
            "bwd": {"flops": 8.0 * pairs,
                    "bytes": 4.0 * q_tile + 4.0 * kv_tile}}
