"""The dots3-note-prev layer's SHARES: the parts that all head shares and all
expert shares of a layer give, with what every chip computes alike counted
once, add up to the uncut reference's layer (``benchmark/reference/
dots3_note_prev.py``), for both attention shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import dots3_note_prev as ref
from paddle_tpu.models import dots3
from paddle_tpu.parallel import transformer as T

S = 64


def close(got, want, tolerance=1e-5):
    got, want = np.asarray(got), np.asarray(want)
    return np.max(np.abs(got - want)) <= tolerance * max(
        1e-3, np.max(np.abs(want)))


def _share_of(pl, whole, at):
    """A head share's leaves out of the uncut layer's ``pl``: the held
    heads' columns of ``wq_b`` and ``wkv_b`` and rows of ``wo``."""
    def heads(leaf, width, axis):
        return jax.lax.slice_in_dim(leaf, at.first_head * width, (
            at.first_head + at.heads_here) * width, axis=axis)
    return dict(pl, wq_b=heads(pl["wq_b"], whole.head_dim, 1),
                wkv_b=heads(pl["wkv_b"], whole.qk_nope_dim
                            + whole.v_head_dim, 1),
                wo=heads(pl["wo"], whole.v_head_dim, 0))


@pytest.mark.parametrize("position", [0, 1], ids=["full", "sliding"])
def test_the_shares_parts_add_up_to_the_uncut_layer(position):
    """Two shares of the heads and four of the experts: the attention
    branches' partial outputs add up to the uncut reference's branch; on
    that sum, the routed parts add up and the shared expert, which every
    share computes alike, counts once: the uncut reference's layer."""
    uncut = dots3.dots3_tiny_config(heads_held_share=1, first_head_share=0,
                                    experts_held=8, first_expert=0)
    params = T.init_transformer_params(jax.random.PRNGKey(3), uncut)
    run = params["params_layers"]["r%d" % position]
    pl = jax.tree.map(lambda a: a[0, 0], run)
    bias = params["router_bias"][position]
    whole = uncut.position(uncut.layer_kinds[position])[0]
    x = jnp.asarray(np.random.RandomState(3).randn(1, S, uncut.hidden),
                    jnp.float32)
    no_ffn = dict(we_down=jnp.zeros_like(pl["we_down"]),
                  ws_down=jnp.zeros_like(pl["ws_down"]))
    with jax.default_matmul_precision("highest"):
        attended = 0.0
        for share in range(2):
            cfg = dots3.dots3_tiny_config(
                heads_held_share=2, first_head_share=share, experts_held=8,
                first_expert=0)
            kind = cfg.layer_kinds[position]
            mine = dict(_share_of(pl, whole, cfg.position(kind)[0]), **no_ffn)
            attended = attended + T.transformer_layer(
                mine, x, cfg, kind, router_bias=bias)[0] - x
        model = dict(ref.model_of(uncut), num_hidden_layers=1)
        o, _, _ = ref.attention_part(
            x[0], {name: pl[name] for name in ref.ATTENTION_LEAVES
                   + (ref.INDEXER_LEAVES if position == 0 else ())},
            model, sliding=position == 1)
        assert close(attended[0], o, 2e-5)
        # the FFN's shares, on the stream the attention branches' sum gives
        h1 = x + attended
        no_attention = dict(pl, wo=jnp.zeros_like(pl["wo"]))
        kind = uncut.layer_kinds[position]
        parts = []
        for share in range(4):
            cfg = dots3.dots3_tiny_config(
                heads_held_share=1, first_head_share=0, experts_held=2,
                first_expert=2 * share)
            mine = dict(no_attention, **{
                name: pl[name][2 * share:2 * share + 2]
                for name in ("we_gate_up", "we_down")})
            parts.append(T.transformer_layer(
                mine, h1, cfg, cfg.layer_kinds[position],
                router_bias=bias)[0] - h1)
        alike = T.transformer_layer(
            dict(mine, we_down=jnp.zeros_like(mine["we_down"])), h1, cfg,
            cfg.layer_kinds[position], router_bias=bias)[0] - h1
        m = ref._rms(h1[0], pl["ln2_scale"], uncut.norm_eps)
        routed = ref.moe_part(m, pl["router"], bias, pl["we_gate_up"],
                              pl["we_down"], 0, uncut.experts_per_token,
                              uncut.route_scale)
        shared = ref.gated_ffn(m, pl["ws_gate_up"], pl["ws_down"])
    assert close(alike[0], shared, 2e-5) and np.max(np.abs(shared)) > 1e-3
    assert close(sum(parts)[0] - 3 * alike[0], routed + shared, 2e-5)
    # and a share alone is not the layer: every part matters
    assert not close(parts[0][0], routed + shared, 1e-2)
