"""Model code: device time under the program's scopes ``attention`` (the
five projections, q/k norm's surroundings, rotation, the flash kernels,
``wo``) and ``attn_gate`` (the gate's sigmoid and its product with the
heads' output), all phases, over the device's busy time.  The norms inside
attention carry ``layer_norm`` / ``post_norm`` and are not in it.  On the
v5e XLA fuses the gate's elementwise work into the neighbouring matmuls'
fusions, which carry ``attention``'s name (the first chip trace, PR 45, had
no instruction of ``attn_gate``'s own): its time is then inside
``attention``'s, and the second scope adds what a compiler keeps apart.
``moe_time_share``'s rule on unattributed time; a program whose vocabulary
has no ``attn_gate`` (the parent commit's) reads nothing."""

from ..harness import scope_time
from . import mla_time_share

SCOPES = ("attention", "attn_gate")


def seconds(trace, cell):
    """Device seconds under the two scopes, or None without the table or
    where the program has no gated attention to name."""
    table = scope_time.seconds(trace, cell)
    if table is None:
        return None
    from paddle_tpu.monitor import devscope

    if "attn_gate" not in devscope.VOCABULARY:
        return None
    return sum(s for (_, at), s in table.items() if at in SCOPES) or None


def read(trace, spans, counters, cell):
    took = seconds(trace, cell)
    if took is None or not mla_time_share.attributed(
            trace, spans, counters, cell, "gated_attn_time_share"):
        return None
    gate = mla_time_share.seconds(trace, cell, "attn_gate") or 0.0
    cell["say"]("gated_attn_time_share: %.6f s under attention + attn_gate, "
                "%.6f s of it under attn_gate" % (took, gate))
    return 100.0 * took / trace.busy_s
