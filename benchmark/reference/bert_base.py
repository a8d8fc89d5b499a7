"""Plain reference for ``bert_base``: the masked-LM forward loss in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``.  No
kernels, no scan, no sharding, nothing imported from the program; it takes
the program's weights by their names in the parameter tree and a batch
(``ids``, ``labels``, ``mask``) and returns the loss as a Python float.

The equations are the ones the program's model defines, which depart from
the published BERT (Devlin et al. 2018) in ways the reference has to share
to be comparable, each noted here:

- pre-LN blocks (layer norm BEFORE attention and FFN, one final layer norm
  before the head) where BERT is post-LN; eps 1e-6;
- token + position embeddings only: no segment embedding, no embedding
  layer norm, no dropout;
- tanh-approximated GELU;
- the head is the tied token embedding applied to the final layer norm:
  no transform layer, no output bias, no next-sentence head;
- loss = sum(nll * mask) / max(sum(mask), 1) over the whole batch.

Sequences are processed in chunks that fit beside the trainer's state.

TOLERANCE is relative, on the scalar loss.  The system computes in bf16
(8 bits of mantissa) with f32 accumulation; the per-token error is random
and the loss averages it over B*P predicted tokens, so the two agree far
better than one bf16 ulp.  Set from the chip: over 54 runs (PR 22, one and
four chips) the relative error lay between 1.2e-7 and 2.9e-5; 2e-4 leaves seven times the largest.  What that catches, measured
by putting the fault into the reference at the published sizes
(``benchmark/tools/ref_sensitivity.py``, B=16): a dropped layer moves the
loss by 1.2e-3, a missing mask by 2.1e-2, a mask shifted by one position by
2.4e-2, inputs left unmasked by 2.5e-2.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

TOLERANCE = 2e-4
CHUNK = 8


def _ln(x, scale, bias, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _layer(x, p, n_heads):
    b, s, e = x.shape
    dh = e // n_heads
    h = _ln(x, p["ln1_scale"], p["ln1_bias"])
    q = (h @ p["wq"] + p["bqkv"][0]).reshape(b, s, n_heads, dh)
    k = (h @ p["wk"] + p["bqkv"][1]).reshape(b, s, n_heads, dh)
    v = (h @ p["wv"] + p["bqkv"][2]).reshape(b, s, n_heads, dh)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, e)
    x = x + o @ p["wo"] + p["bo"]
    h = _ln(x, p["ln2_scale"], p["ln2_bias"])
    y = jax.nn.gelu(h @ p["w1"] + p["b1"], approximate=True)
    return x + y @ p["w2"] + p["b2"]


def _head(x, scale, bias, emb, labels, mask):
    logits = _ln(x, scale, bias) @ emb.T
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - picked) * mask), jnp.sum(mask)


def loss(params, batch, model):
    n_heads = int(model["num_attention_heads"])
    f32 = lambda a: jnp.asarray(np.asarray(a, np.float32))  # noqa: E731
    layer = jax.jit(_layer, static_argnums=2)
    head = jax.jit(_head)
    with jax.default_matmul_precision("highest"):
        tok, pos = f32(params["tok_emb"]), f32(params["pos_emb"])
        layers = {k: f32(v) for k, v in params["params_layers"].items()}
        n_layers = layers["wq"].shape[0]
        lnf = f32(params["lnf_scale"]), f32(params["lnf_bias"])
        ids, labels = np.asarray(batch["ids"]), np.asarray(batch["labels"])
        mask = np.asarray(batch["mask"], np.float32)
        total = count = 0.0
        for lo in range(0, ids.shape[0], CHUNK):
            sl = slice(lo, lo + CHUNK)
            x = tok[ids[sl]] + pos[: ids.shape[1]][None]
            for i in range(n_layers):
                x = layer(x, {k: v[i] for k, v in layers.items()}, n_heads)
            t, c = head(x, lnf[0], lnf[1], tok, jnp.asarray(labels[sl]),
                        jnp.asarray(mask[sl]))
            total, count = total + float(t), count + float(c)
    return total / max(count, 1.0)
