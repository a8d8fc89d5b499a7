"""Plain reference for ``resnet50``: the training-mode forward loss of a
ResNet (He et al. 2015) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  Nothing imported from the
program; it takes the program's weights by their names in the parameter
tree and a batch (``image`` NHWC, ``label``) and returns the softmax
cross-entropy, averaged over the batch, as a Python float.

Follows the paper's Table 1 and Figure 5 with the one change every current
implementation makes and the program shares: in a bottleneck block the
stride sits on the 3x3 convolution, not the first 1x1 ("v1.5").  The stem is
the plain 7x7 stride-2 convolution with SAME padding (the program computes
it by a space-to-depth transform, which this checks), then a 3x3 stride-2
max pool.  Batch norm is in training mode: statistics of the batch itself
over (N, H, W), biased variance, eps 1e-5; the running averages play no
part in the loss.  The whole batch goes through at once, because the batch
statistics couple its images; convolutions are jitted one block at a time.

TOLERANCE is relative, on the scalar loss.  The system computes in bf16;
batch norm renormalizes after every convolution, so rounding does not grow
without bound with depth, and the loss averages over the batch.  Set from
the chip: over 61 runs (PR 22, both cells) the relative
error lay between 3.9e-5 and 3.0e-3, most under 2e-3; 1e-2 leaves three
times the largest.  The scalar loss of randomly initialised weights is a
WEAK witness, and the file says so rather than pretend: putting faults into
the reference at the published sizes (``benchmark/tools/ref_sensitivity.py``,
B=32) moved the loss by 1.3e-3 for a dropped block, 7.9e-3 for a block given
another block's weights and 5e-4 for labels shifted by one, all inside the
bound.  What it does catch is gross: non-finite values, eval-mode batch norm
on fresh running averages, a lost normalization or a wrong class count.
The CPU tests compare at 2e-4 in float32, where both sides are exact, and
there a changed stem, stride or block fails.  A tighter chip-side check
needs logits from the program (PERF.md, Open questions).
"""

import jax
import jax.numpy as jnp
import numpy as np

TOLERANCE = 1e-2
DN = ("NHWC", "HWIO", "NHWC")


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(x, w, (stride, stride), "SAME",
                                        dimension_numbers=DN)


def _bn(x, p, eps=1e-5):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.var(x, axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _stem(x, w, bn):
    x = jax.nn.relu(_bn(_conv(x, w, 2), bn))
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1), "SAME")


def _block(x, p, stride):
    shortcut = x
    if "conv3" in p:                                    # bottleneck
        y = jax.nn.relu(_bn(_conv(x, p["conv1"], 1), p["bn1"]))
        y = jax.nn.relu(_bn(_conv(y, p["conv2"], stride), p["bn2"]))
        y = _bn(_conv(y, p["conv3"], 1), p["bn3"])
    else:                                               # basic
        y = jax.nn.relu(_bn(_conv(x, p["conv1"], stride), p["bn1"]))
        y = _bn(_conv(y, p["conv2"], 1), p["bn2"])
    if "proj" in p:
        shortcut = _bn(_conv(x, p["proj"], stride), p["bnp"])
    return jax.nn.relu(y + shortcut)


def _head(x, w, b, labels):
    logits = jnp.mean(x, axis=(1, 2)) @ w + b
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def loss(params, batch, model):
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.asarray(np.asarray(a, np.float32)), t)
    block = jax.jit(_block, static_argnums=2)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(np.asarray(batch["image"], np.float32))
        x = jax.jit(_stem)(x, f32(params["conv0"]), f32(params["bn0"]))
        stage = 0
        while "s%d_b0" % stage in params:
            i = 0
            while "s%d_b%d" % (stage, i) in params:
                stride = 2 if (i == 0 and stage > 0) else 1
                x = block(x, f32(params["s%d_b%d" % (stage, i)]), stride)
                i += 1
            stage += 1
        out = jax.jit(_head)(x, f32(params["fc_w"]), f32(params["fc_b"]),
                             jnp.asarray(np.asarray(batch["label"])))
    return float(out)
