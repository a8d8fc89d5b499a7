"""The proof that a refactor left a program alone: the StableHLO digests of
every tiny trainer's two programs (``<label>.step``, ``<label>.run_steps``),
one case a trainer.  A PR that means to leave the step programs as they are
runs this file before anything else; a PR that means to change one re-takes
that one's digests and says so in the comment below."""

import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu.models import (bert, brumby, dots3, jamba,  # noqa: E402
                               keye_vl2, kimi_linear,
                               lfm2, mistral4, nemotron_h, olmoe, ouro,
                               resnet, smallthinker, solar_open2, trinity)
from paddle_tpu.parallel import decoder  # noqa: E402
from paddle_tpu.parallel.mesh import MeshSpec  # noqa: E402
from paddle_tpu.parallel.train import stack_batches  # noqa: E402


# the first 16 hex digits of the sha256 of ``lower(...).as_text()`` of each
# older tiny transformer's two programs (remat on, seed 3, batch 2, two
# staged batches), taken on the parent commit (03fc114): what this PR's
# options, off, leave as it was, byte for byte.  A PR that changes one of
# these programs on purpose takes the digests anew: PR 40 did for the three
# sparse decoders, whose expert layers sum back through the row kernel
# (``kernels/moe_rows.py``), and PR 41 for LFM2 alone, whose grouped heads of
# 64 ride the flash sweeps stacked (``kernels/flash_attention.py``: OLMoE's
# and SmallThinker's held through it), and PR 46 for SmallThinker and LFM2,
# whose held share's 256 rows in two groups now go in 128-row tiles
# (``moe._tiling``; OLMoE's tiny layer is one tile either way and held
# through it), and PR 47 for SmallThinker, LFM2 and Brumby, whose tiny q and
# k are whole lane blocks (heads of 128, or two of 64) and so go through the
# row kernel (``kernels/qk_rope.py``, their three projections through
# ``_project``; OLMoE's 4 heads of 16 are half a lane block and keep the
# plain matmuls and the ``rms_norm`` / ``rope`` lines, in their old order);
# BERT's are still 03fc114's.  Mistral's and Trinity's joined the table in
# PR 48, taken on its parent (3ea462c); Jamba's and Nemotron-H's in PR 54, on
# its parent (37c698c): the passes of a looped stack (``loop_passes``) and
# the one rule for the dense gated FFN (``cfg.dense_stack``) left all nine.
# PR 55 took the six anew whose tiny backward is a several-block flash
# backward at heads of whole lane blocks (SmallThinker, LFM2, Mistral,
# Trinity, Jamba, Nemotron-H): its ``delta`` is the row kernel's
# (``kernels/flash_delta.py``) where it was three ``jnp`` lines; BERT's (one
# kv block: ``delta`` inside the kernel), OLMoE's (heads of 16: no flash
# call) and Brumby's (retention) stand.  Ouro's and ResNet's joined in PR 56,
# when the table got this file of its own, taken on that PR's parent
# (ca40cf3) before any other edit: Ouro's at three passes over two layers,
# ResNet's at depth 18 on 32 x 32 images (it has no remat to turn on), so
# that all eleven trainers are held.  PR 56 re-took none of the eighteen.
# PR 57 took Mistral's two anew ON PURPOSE: its tiny heads of 96 + 32 are
# whole lane blocks, so the latent q and k go through the row kernel's
# ``pairs`` convention (``kernels/qk_rope.py``) and k and v come out of
# ``wkv_b``'s own columns (``transformer._latent_columns``); the twenty others
# stand (the six rotary decoders' ``qk_rope`` calls lower to the text, and on
# the chip to the Mosaic modules, they had:
# ``tests/test_chip_compile_rows.py::QK_ROPE_MOSAIC``).  PR 58 re-took NONE
# of the twenty-two: the latent form's new branches (no positions, no query
# latent, a value width of its own), the layer kind KDA and the flash
# kernels' value-width mode left every older program's text as it was,
# Mistral's included; Kimi-Linear's two joined, taken on that PR's tree.  PR 61
# re-took NONE of the twenty-four: the flash kernels' masked mode, the
# indexer, the position streams of ``rope`` / ``angle_tables`` and the
# checkpoint policy of a layer with an indexer left every older program's
# text as it was; Keye-VL-2.0's two joined, taken on that PR's tree.  PR 62
# took Keye's two anew ON PURPOSE (the layer's output comes from the pass
# with the statistic known, ``dsa_attend_kl``, whose backward runs the
# scores' backward itself, and the policy keeps three names); the
# twenty-four others stand: no other configuration traces
# ``_sparse_attention`` or ``kernels/indexer.py``.  PR 63 re-took NONE of
# the twenty-six: an attention position that reads its OWN shape
# (``TransformerConfig.position``, which hands a plain ``(window, rotary)``
# the configuration itself), the latent form under a window, an indexer and
# a head-wise gate, a share of the heads and the flash and indexer kernels'
# value-width modes left every older program's text as it was, Keye's and
# Kimi-Linear's included; dots3-note-prev's two joined, taken on that PR's
# tree.  PR 64 took Keye's and dots3's four anew ON PURPOSE (the two masked
# sweeps are kernels of ``kernels/indexer.py``, a (tile, key/value head) a
# grid step, the forward's only output the statistic, and ``dsa_lse`` reads
# no value); the twenty-four others stand: the ``mask=`` mode left
# ``kernels/flash_attention.py`` and took nothing of theirs with it.  PR 66
# took dots3's two and Kimi-Linear's two anew ON PURPOSE (``_latent_qkv_lanes``
# sends q, where rotated, and k through ``kernels/qk_rope.py`` at a head of
# two lane blocks, the projections through ``_project``); the twenty-four
# others stand: the row kernel at a head of one lane block traces the
# operations it did (Mistral's ``pairs`` calls and the rotate-half ones).
# PR 68 took the five GROUPED decoders' ten anew ON PURPOSE (SmallThinker,
# LFM2, Trinity, Jamba, Nemotron-H: a several-block sweep's grid step holds
# ``heads_a_step`` query head-blocks of a group, looped inside it, forward
# and fused backward, ``kernels/flash_attention.py``); the eighteen others
# stand: an ungrouped call is one head-block a step and traces the
# operations it did (Mistral's, Ouro's, Kimi-Linear's and dots3's several-
# block sweeps among them), and Keye's sweeps only import ``heads_a_step``.
# Solar-Open2's two joined in PR 69 (PR 67 brought the trainer and no
# digest), taken on that PR's parent (2e410fb) before any other edit, so that
# all fifteen trainers are held; PR 69 re-took NONE of the twenty-eight
# (``make_train_step`` lost an argument every caller left at ``None``).
# PR 70 took Mistral's, Kimi-Linear's and dots3's six anew ON PURPOSE (the
# three tiny trainers whose ungrouped heads of a whole lane block run over
# several blocks: a several-block sweep's grid step holds ``heads_a_step``
# adjacent head-blocks of the batch row, each with k and v of its own,
# forward and fused backward, ``kernels/flash_attention.py``; they were
# ffaa7601578581ff / 9100e873b1250116, 5a34b11e1dde7e94 / e5f0e15d922e959c
# and d7d1e352464fcf9e / 73b06e01bdc7bd2d); the twenty-four others stand:
# a grouped call keeps PR 68's step, OLMoE's and Ouro's tiny heads of 16
# are no whole lane block (one head-block a row, or no packed call), one
# block is the one-block kernel (BERT), and Keye's sweeps only import
# ``heads_a_step``.
# PR 74 took the twenty-eight of the fourteen transformers anew ON PURPOSE,
# BERT's two among them (the LM head is ONE rule now,
# ``transformer._weighted_vocab_nll``: its forward rule makes a row block's
# logits once and its gradient from them, where the backward made the
# logits a second time; every tiny trainer's step holds that head).  Only
# ResNet's two stand: it has no head.
PROGRAMS ={"bert.step": "fbcac7ce5f885d9f",
            "bert.run_steps": "ff41116e2eb1f9c3",
            "olmoe.step": "a2b31202767bd64f",
            "olmoe.run_steps": "f9095ed87bed8872",
            "smallthinker.step": "eed33bf02fd7e549",
            "smallthinker.run_steps": "118b14b74a0e995b",
            "lfm2.step": "ef90aa9703580a38",
            "lfm2.run_steps": "94bd3e16a6893814",
            "brumby.step": "b7fe0f13d6cbfa9d",
            "brumby.run_steps": "77498fc4f8dfe0c8",
            "mistral4.step": "5595ab2f0617a54c",
            "mistral4.run_steps": "45acba89ab56b464",
            "trinity.step": "d9f608bb20f14962",
            "trinity.run_steps": "08a757c561d10e83",
            "jamba.step": "d35f0d189b61817d",
            "jamba.run_steps": "b16c3b496fd70013",
            "nemotron_h.step": "88373ab959c19e9f",
            "nemotron_h.run_steps": "f9ce422c2d0feeaa",
            "ouro.step": "b7bdc57761636777",
            "ouro.run_steps": "43150861e7804981",
            "resnet.step": "350db1fba0d68284",
            "resnet.run_steps": "dc9dd853700f9ab9",
            "kimi_linear.step": "c72682d00ab0208b",
            "kimi_linear.run_steps": "9cb5df3feb89fc2b",
            "keye_vl2.step": "13f55d07016271a6",
            "keye_vl2.run_steps": "9561d40a58e556a0",
            "dots3.step": "edc943826b841628",
            "dots3.run_steps": "910ec672392d4f33",
            "solar_open2.step": "3d067b30a120bd2c",
            "solar_open2.run_steps": "da4fe93f40e355ec"}
OLDER = {"bert": (bert.build_bert_trainer, bert.bert_tiny_config, 32),
         "olmoe": (olmoe.build_olmoe_trainer, olmoe.olmoe_tiny_config, 32),
         "smallthinker": (smallthinker.build_smallthinker_trainer,
                          smallthinker.smallthinker_tiny_config, 64),
         "lfm2": (lfm2.build_lfm2_trainer, lfm2.lfm2_tiny_config, 64),
         "brumby": (brumby.build_brumby_trainer, brumby.brumby_tiny_config,
                    64),
         "mistral4": (mistral4.build_mistral4_trainer,
                      mistral4.mistral4_tiny_config, 64),
         "trinity": (trinity.build_trinity_trainer,
                     trinity.trinity_tiny_config, 64),
         "jamba": (jamba.build_jamba_trainer, jamba.jamba_tiny_config, 64),
         "nemotron_h": (nemotron_h.build_nemotron_h_trainer,
                        nemotron_h.nemotron_h_tiny_config, 64),
         "ouro": (ouro.build_ouro_trainer, ouro.ouro_tiny_config, 64),
         "resnet": (resnet.build_resnet_trainer,
                    lambda remat: resnet.resnet_tiny_config(), 32),
         "kimi_linear": (kimi_linear.build_kimi_linear_trainer,
                         kimi_linear.kimi_linear_tiny_config, 64),
         "keye_vl2": (keye_vl2.build_keye_vl2_trainer,
                      keye_vl2.keye_vl2_tiny_config, 64),
         "dots3": (dots3.build_dots3_trainer, dots3.dots3_tiny_config, 64),
         "solar_open2": (solar_open2.build_solar_open2_trainer,
                         solar_open2.solar_open2_tiny_config, 64)}


def digests(names):
    """``{program: the first 16 hex digits of its text's sha256}`` of the
    two programs of each named trainer, in THIS process."""
    out = {}
    for name in names:
        build, config, seq = OLDER[name]
        tr = build(config(remat=True), MeshSpec(dp=1), seed=3,
                   devices=jax.devices()[:1])
        ids = np.zeros((2, seq), np.int32)
        batch, specs = {"ids": ids}, decoder.BATCH_SPECS
        if name == "bert":
            batch = {"ids": ids, "labels": ids,
                     "mask": np.ones((2, seq), np.float32)}
            specs = bert.batch_specs(tuple(batch))
        if name == "resnet":
            batch = {"image": np.zeros((2, seq, seq, 3), np.float32),
                     "label": np.zeros((2,), np.int32)}
            specs = resnet.BATCH_SPECS
        one = {k: jnp.asarray(v) for k, v in batch.items()}
        many = stack_batches(tr.mesh, specs, [batch, batch])
        for label, fn, args in (("step", tr.step_fn, (tr.state, one, 1e-3)),
                                ("run_steps", tr.multi_fn,
                                 (tr.state, many, 1e-3))):
            out["%s.%s" % (name, label)] = hashlib.sha256(
                fn.lower(*args).as_text().encode()).hexdigest()[:16]
    return out


# ONE worker takes this file, so ``fresh``'s processes run once: the tier-1
# command's ``--dist loadfile`` keeps a file together, and under
# ``--dist loadgroup`` this mark does
pytestmark = pytest.mark.xdist_group("program_digests")
PROCESSES = 3       # the trainers' digests are taken in as many at a time


@pytest.fixture(scope="module")
def fresh():
    """Every program's digest, taken in a process that has traced nothing
    else (``PROCESSES`` of them side by side, each a share of the trainers:
    the suite's six workers leave cores idle, and one process took 160 to
    250 s of this file's worker).  A lowered text names its private
    functions in the order the module met them, and two call sites share
    one only where the process's trace cache hands both the same jaxpr: in a
    worker that has traced other files' programs first, a MoE step lowered
    with one more ``_where`` and every later symbol renumbered (PR 61 met it
    when two new test files moved this file to another xdist worker; the
    programs were the parent's).  What a PR did to a program is what a fresh
    process lowers."""
    names = list(OLDER)
    runs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)] + names[i::PROCESSES],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for i in range(PROCESSES)]
    out = {}
    for run in runs:
        stdout, stderr = run.communicate()
        assert run.returncode == 0, stderr[-3000:]
        out.update(json.loads(stdout.splitlines()[-1]))
    return out


@pytest.mark.parametrize("name", list(OLDER))
def test_the_older_transformers_programs_lower_to_the_parent_s_text(fresh,
                                                                    name):
    for label in ("step", "run_steps"):
        program = "%s.%s" % (name, label)
        assert fresh[program] == PROGRAMS[program], (
            "the program %s lowers to another text than the table holds: "
            "re-take the digest only if the PR means to change this program"
            % program)


if __name__ == "__main__":
    print(json.dumps(digests(sys.argv[1:] or list(OLDER))))
