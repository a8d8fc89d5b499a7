"""What one layer's attention needs, for the flash kernels' roofline."""


def required(batch, seq, hidden, itemsize=2, causal=False):
    """FLOPs and HBM bytes of one layer's attention over ``batch``
    sequences, forward and backward apart.

    Forward: QK^T and PV, 4*S^2*E per sequence; reads q, k, v and writes o.
    Backward: dV, dP, dQ, dK, 8*S^2*E; the recomputed QK^T does not count;
    reads q, k, v, o, do and writes dq, dk, dv.  The f32 row statistics are
    S*H*4 bytes, under 1 % of the rest, and left out.  Causal halves the
    FLOPs."""
    half = 0.5 if causal else 1.0
    tile = batch * seq * hidden * itemsize
    return {"fwd": {"flops": 4.0 * batch * seq * seq * hidden * half,
                    "bytes": 4.0 * tile},
            "bwd": {"flops": 8.0 * batch * seq * seq * hidden * half,
                    "bytes": 8.0 * tile}}
