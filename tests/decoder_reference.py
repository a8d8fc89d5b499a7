"""What the decoders' reference tests (``tests/test_*_reference.py``) share:
a model's ``Case`` (its module, its plain reference, sizes, leaves and
tolerances), the helpers every file had a copy of, the artefacts a file
builds ONCE (``both``: program and reference on the same weights;
``witnessed``: the trainer's own logits at the witness's positions;
``trained``: the pair of trainers behind the ``run_steps``, counter and scope
tests) and ``common(case)``, the repeated tests, which a model's file
installs under their names (``globals().update(common(CASE))``) so that
pytest collects them IN THAT FILE.  Not collected itself.

A model's file stays a file of its own on purpose: ``--dist loadfile`` gives
a file to one worker, so a file a model spreads the reference tests over the
workers.  A new configuration costs a ``Case`` and its mechanism's own tests
(``.claude/skills/verify/SKILL.md``)."""

import copy
import dataclasses
import importlib
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.monitor import devscope  # noqa: E402
from paddle_tpu.parallel import decoder, optim, transformer as T  # noqa: E402
from paddle_tpu.parallel.mesh import MeshSpec  # noqa: E402
from paddle_tpu.parallel.rules import leaf_paths  # noqa: E402
from paddle_tpu.parallel.train import stack_batches  # noqa: E402

NUMBERS = {2: "two", 3: "three"}


@dataclasses.dataclass(frozen=True)
class Witness:
    """How a file reads the program at the witness's positions."""
    cfg: dict = None            # the trainer's configuration; None: ``both``'s
    model: dict = dataclasses.field(default_factory=dict)   # over the case's
    seed: int = 9               # of the ids; None: ``both``'s ids
    rows: int = 1               # sequences read (the cell's batch); None: B
    from_logits: bool = False   # ``both``'s own logits, no trainer
    faults: tuple = None        # None: all of the reference's but the last
    floors: dict = dataclasses.field(default_factory=dict)  # fault: x tol
    pieces: dict = dataclasses.field(default_factory=dict)  # patched


@dataclasses.dataclass(frozen=True)
class Case:
    """A model's configuration of the harness."""
    name: str                   # ``paddle_tpu.models.<name>``, and the label
    reference: types.ModuleType     # ``benchmark.reference.<model>``
    model: dict                 # the published keys the reference reads
    leaves: tuple               # every leaf a gradient reaches
    B: int = 2
    S: int = 64
    tol: float = 1e-5           # of the loss, relative
    each: float = None          # of a logit row or a gradient element
    #                             against its array's largest; None: ``tol``,
    #                             the logits' absolute
    grad_rtol: float = 1e-4
    aux: bool = False           # the loss function returns (loss, stepped)
    biased: bool = False        # ``router_bias`` beside the leaves: no
    #                             gradient reaches it
    off_one: tuple = ("scale", "_norm")     # leaves moved off their seed
    gain: object = None         # keystr -> factor of any other leaf
    rows: int = None            # sequences ``both`` compares; None: B
    forward: object = None      # (params, ids) -> (loss, seen) of the
    #                             reference; None: the loss alone
    one_program: bool = False   # ``both``'s program returns the logits too
    loss_floor: float = 0.0     # the loss is more than the cross entropy's
    mechanism: object = None    # () -> None: what the tiny size keeps
    logits: bool = True         # install the logits test
    grad_test: str = "test_gradient_of_every_leaf_equals_the_reference"
    leaves_test: str = "test_the_leaves_tested_are_all_there_are"
    spec_configs: tuple = None  # the tiny configurations whose trees the
    #                             specs follow; None: no such test
    bfloat16: bool = False      # install the ``bfloat16_throughout`` test
    pieces: dict = None         # the reference's block sizes, cut small
    pieces_hold: tuple = ("loss", "grads")
    witness: Witness = None
    steps: int = None           # batches ``run_steps`` is held over
    trained_cfg: dict = dataclasses.field(
        default_factory=lambda: {"remat": True})
    steps_atol: float = 1e-6
    counters: object = None     # install the counters test: True reads
    #                             ``trained``'s session, a configuration its
    #                             own trainer's (where the readings depend on
    #                             ``remat``: a cached trace counts once)
    also: dict = dataclasses.field(default_factory=dict)    # key -> more
    fields: object = None       # ids [b, S] -> the batch's other fields (a
    #                             block-diffusion batch's noise); None: none

    @property
    def module(self):
        return importlib.import_module("paddle_tpu.models." + self.name)

    def config(self, **cfg):
        return getattr(self.module, self.name + "_tiny_config")(**cfg)


def trainer(case, seed=3, optimizer=None, dp=1, **cfg):
    return getattr(case.module, "build_%s_trainer" % case.name)(
        case.config(**cfg), MeshSpec(dp=dp),
        optimizer=optimizer or optim.adamw(), seed=seed,
        devices=jax.devices()[:dp])


def ids(case, seed=0, n=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 256, (case.B, case.S)).astype(np.int32)
            for _ in range(n)]


def batch_of(case, ids):
    """The batch of ``ids``: with what ``case.fields`` makes beside them."""
    return dict({"ids": ids}, **(case.fields(ids) if case.fields else {}))


def read_of(case, ids):
    """What a reader of ONE input takes (``logits_at``, a reference's
    ``forward``): the ids, or the whole batch where it has other fields."""
    return batch_of(case, ids) if case.fields else ids


def moved(case, params):
    """Seeded weights with the leaves named ``case.off_one`` (the norm
    scales, ...) moved off their seeds, so that a missing or misplaced one
    shows, and the others times ``case.gain`` (a router steep enough that
    the weights are not all alike, ...)."""
    rng = np.random.RandomState(11)

    def one(path, a):
        name = jax.tree_util.keystr(path)
        if any(word in name for word in case.off_one):
            return np.asarray(a) * rng.uniform(0.5, 1.5, a.shape).astype("f4")
        gain = case.gain(name) if case.gain else 1.0
        return np.asarray(a) if gain == 1.0 else np.asarray(a) * gain

    return jax.tree_util.tree_map_with_path(one, params)


def steep(*words, by=3.0):
    """A ``Case.gain``: the leaves whose name holds one of ``words`` times
    ``by`` (a router steep enough that the weights are not all alike)."""
    return lambda name: by if any(w in name for w in words) else 1.0


def leaf(tree, path):
    """The leaf at ``a/b/c``; a one-stack tree's layer leaves by their name
    alone."""
    if path.split("/")[0] not in tree:
        tree = tree["params_layers"]
    for part in path.split("/"):
        tree = tree[part]
    return tree


def staged(tr, batches):
    return stack_batches(tr.mesh, decoder.batch_specs(tr.cfg), batches)


def loss_agrees(got, want, tol):
    assert abs(float(got) - float(want)) / float(want) < tol


def leaves_agree(got, want, rtol, atol):
    """Every leaf of two trees, ``atol`` against the leaf's largest."""
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=rtol, atol=atol * max(np.abs(w).max(), 1e-30))


class Unreadable:
    """Ids that no one may read back."""

    def __init__(self, case):
        self.shape, self.size = (case.B, case.S), case.B * case.S

    def __array__(self, *a, **k):
        raise AssertionError("the ids were read back with no monitor on")


@dataclasses.dataclass
class Both:
    """Program and reference on the same weights; unpacks as ``cfg, params,
    ids, (loss, gradients), (the reference's loss, gradients)``."""
    tr: object
    cfg: object
    params: dict
    ids: np.ndarray
    got: tuple
    want: tuple
    stepped: object = None      # the loss function's second output
    logits: object = None       # the program's, where it returns them
    seen: object = None         # the reference's second output

    def __iter__(self):
        return iter((self.cfg, self.params, self.ids, self.got, self.want))


def both(case):
    tr = trainer(case)
    cfg, params = tr.cfg, moved(case, tr.state["params"])
    rows = ids(case)[0][:case.rows]
    loss_fn = decoder.make_loss_fn(cfg)

    def program(p):
        out = loss_fn(p, jax.tree.map(jnp.asarray, batch_of(case, rows)))
        loss, stepped = out if case.aux else (out, None)
        logits = None
        if case.one_program:
            x, _ = decoder.forward(p, jnp.asarray(rows), cfg)
            logits = T.head_logits(p, x, cfg)
        return loss, (stepped, logits)

    def reference(p):
        if case.forward:
            return case.forward(p, rows)
        return case.reference.forward(p, rows, case.model,
                                      keep_logits=False)[0], None

    (loss, (stepped, logits)), grads = jax.jit(
        jax.value_and_grad(program, has_aux=True))(params)
    (want, seen), want_grads = jax.value_and_grad(reference, has_aux=True)(
        jax.tree.map(jnp.asarray, params))
    return Both(tr, cfg, params, rows, (loss, grads), (want, want_grads),
                stepped, logits, seen)


def at_weights(tr, params):
    """A copy of ``tr`` that holds ``params``; ``tr`` keeps its own."""
    tr = copy.copy(tr)
    tr.state = dict(tr.state, params=jax.tree.map(jnp.asarray, params))
    return tr


def witnessed(case, both):
    """``(params, ids, the program's logits at the witness's positions, the
    reference's model)``: the trainer's OWN forward (``logits_at``), its
    weights moved as ``both``'s."""
    w = case.witness
    at = case.reference.witness_positions(case.S)
    model = dict(case.model, **w.model)
    if w.from_logits:
        return both.params, both.ids, np.asarray(both.logits)[:, at], model
    if w.cfg is None:           # ``both``'s configuration and seed: its trainer
        tr, params = both.tr, both.params
    else:
        tr = trainer(case, **w.cfg)
        params = moved(case, tr.state["params"])
    tr = at_weights(tr, params)
    rows = both.ids if w.seed is None else ids(case, seed=w.seed)[0][:w.rows]
    return params, rows, np.asarray(
        tr.logits_at(read_of(case, rows), at)), model


def scope_map(tr):
    """``tr``'s ``run_steps`` program's scope map after a run: of the live
    programs under its label (``label``, ``label#2``, ... in the order they
    were registered) the last."""
    maps = devscope.scope_maps()
    return maps[[key for key in maps
                 if key.split("#")[0] == tr.label + ".run_steps"][-1]]


@dataclasses.dataclass
class Trained:
    """Two trainers of one seed over the same batches: ``one`` took a step a
    batch, ``scan`` ONE ``run_steps`` under a monitor session."""
    scan: object
    batches: list
    singly: list            # ``one``'s losses
    after: list             # ``one``'s parameters: seeded, after each step
    scanned: np.ndarray     # ``scan``'s losses
    params: dict            # ``scan``'s parameters after the call
    rows: list              # the session's registry, as ``snapshot()`` rows
    names: dict = None      # ``scan``'s ``run_steps`` program's scope map

    def value(self, name, **labels):
        """The one row of ``name`` whose labels hold ``labels``."""
        got = [r["value"] for r in self.rows if r["name"] == name and all(
            str((r["labels"] or {}).get(k)) == str(v)
            for k, v in labels.items())]
        assert len(got) == 1, (name, labels, got)
        return got[0]

    def scopes(self):
        return {devscope.classify(op) for op in self.names.values()}


def observed(case, tmp, seed, n, **cfg):
    """``(trainer, batches, losses, the registry's rows)`` of ONE
    ``run_steps`` over ``n`` batches under a monitor session of its own."""
    batches = [batch_of(case, i) for i in ids(case, seed=seed, n=n)]
    tr = trainer(case, **cfg)
    assert monitor.active() is None
    tr._observe({"ids": Unreadable(case)})      # off a session: nothing runs
    assert tr._probe_fn is None
    mon = monitor.enable(str(tmp), flight=False)
    try:
        mon.registry.reset()
        losses = np.asarray(tr.run_steps(staged(tr, batches), 1e-3))
        return tr, batches, losses, mon.registry.snapshot()
    finally:
        monitor.disable()


def trained(case, tmp):
    scan, batches, scanned, rows = observed(case, tmp, 5, case.steps,
                                            **case.trained_cfg)
    one = trainer(case, **case.trained_cfg)

    def now():
        return jax.tree.map(np.asarray, one.state["params"])

    after, singly = [now()], []
    for batch in batches:
        singly.append(float(one.step(batch, 1e-3)))
        after.append(now())
    return Trained(scan, batches, singly, after, scanned,
                   jax.tree.map(np.asarray, scan.state["params"]), rows,
                   scope_map(scan))


def common(case):
    """The fixtures and the repeated tests of ``case``, by the names a
    model's file installs them under."""
    reference, tol = case.reference, case.tol
    each = case.each or tol
    out = {}

    def more(key, *args):
        if key in case.also:
            case.also[key](*args)

    def install(fn, name=None):
        if name and hasattr(fn, "__name__"):
            fn.__name__ = name      # what a traceback shows
        out[name or fn.__name__] = fn
        return fn

    @pytest.fixture(scope="module", name="both")
    def both_fixture():
        """Loss and gradients of program and reference on the same
        weights."""
        return both(case)
    install(both_fixture, "both")

    if case.mechanism:
        @install
        def test_the_tiny_configuration_keeps_every_mechanism():
            cfg = case.config()
            assert cfg.vocab_size == 256 and cfg.max_seq >= case.S
            case.mechanism()

    @install
    def test_loss_equals_the_reference(both):
        loss_agrees(both.got[0], both.want[0], tol)
        assert float(both.got[0]) > case.loss_floor

    if case.logits:
        @install
        def test_every_position_s_logits_equal_the_reference(both):
            cfg, params = both.cfg, both.params
            if case.one_program:
                got, want = both.logits, both.seen
            else:
                x, _ = jax.jit(lambda p, i: decoder.forward(p, i, cfg))(
                    params, both.ids)
                head = params["tok_emb" if cfg.tie_head else "lm_head"]
                got = T.rms_norm(x, params["lnf_scale"], cfg.norm_eps) @ head.T
                _, want = reference.forward(params, both.ids, case.model)
            want = np.stack(want)
            np.testing.assert_allclose(
                got, want, rtol=1e-4,
                atol=case.each * np.abs(want).max() if case.each else tol)

    @pytest.mark.parametrize("path", case.leaves)
    def gradient_test(both, path):
        g, w = (np.asarray(leaf(t, path)) for t in (both.got[1], both.want[1]))
        assert g.shape == leaf(both.params, path).shape, path
        assert np.abs(w).max() > 0, path
        np.testing.assert_allclose(g, w, rtol=case.grad_rtol,
                                   atol=each * np.abs(w).max())
    install(gradient_test, case.grad_test)

    if case.leaves_test:
        def leaves_test(both):
            params = both.params
            paths, _, _ = leaf_paths(params)
            want = sorted(p if p.split("/")[0] in params
                          else "params_layers/" + p for p in case.leaves)
            assert sorted(paths) == sorted(
                want + ["router_bias"] * case.biased)
            if case.biased:     # it decides who is chosen, nothing else
                assert not np.asarray(both.got[1]["router_bias"]).any()
                assert not np.asarray(both.want[1]["router_bias"]).any()
            more("leaves", both)
        install(leaves_test, case.leaves_test)

    if case.spec_configs is not None:
        @install
        def test_sharding_specs_and_gradient_syncs_follow_the_tree():
            for kw in case.spec_configs:
                cfg = case.config(**kw)
                params = jax.eval_shape(lambda: T.init_transformer_params(
                    jax.random.PRNGKey(0), cfg))
                for tree in (T.transformer_param_specs(cfg),
                             T.grad_sync_axes(cfg)):
                    assert jax.tree.structure(
                        tree, is_leaf=lambda x: isinstance(x, (tuple, T.P))
                    ) == jax.tree.structure(params)
            more("specs", T.transformer_param_specs(cfg))

    if case.bfloat16:
        @install
        def test_bfloat16_throughout_moves_the_reference_s_loss(both):
            want = float(both.want[0])
            bad = reference.loss(both.params, batch_of(case, both.ids),
                                 case.model, faults=("bfloat16_throughout",))
            assert abs(bad - want) / want > 2 * tol
            more("bfloat16", both)

    if case.pieces:
        @install
        def test_the_reference_in_small_pieces_equals_itself_whole(
                both, monkeypatch):
            """At the tiny size a sequence is one block of rows, the head
            one chunk of columns.  Cut as the published size cuts them
            (several row blocks, chunks that do not divide the vocabulary,
            an expert at a time), the reference gives the same loss, and
            where the file holds them the same logits and gradient."""
            params = jax.tree.map(jnp.asarray, both.params)
            logits = "logits" in case.pieces_hold
            if logits:
                _, whole = reference.forward(params, read_of(case, both.ids),
                                             case.model)
            for name, value in case.pieces.items():
                monkeypatch.setattr(reference, name, value)

            def run(p):
                return reference.forward(p, read_of(case, both.ids),
                                         case.model, keep_logits=logits)[:2]

            if "grads" in case.pieces_hold:
                (loss, seen), grad = jax.value_and_grad(
                    run, has_aux=True)(params)
                leaves_agree(grad, both.want[1], 1e-4, 1e-5)
            else:
                loss, seen = run(params)
            loss_agrees(loss, both.want[0], 1e-6)
            if logits:
                np.testing.assert_allclose(np.stack(seen), np.stack(whole),
                                           rtol=1e-5, atol=1e-5)

    if case.witness:
        faults = case.witness.faults
        if faults is None:
            faults = reference.FAULTS[:-1]

        @pytest.fixture(scope="module", name="witnessed")
        def witnessed_fixture(both):
            return witnessed(case, both)
        install(witnessed_fixture, "witnessed")

        @install
        @pytest.mark.parametrize("fault", faults)
        def test_the_witness_sees_every_fault(witnessed, fault, monkeypatch):
            """Each fault in the reference moves its logits away from the
            program's by a thousand times what the two differ by when both
            are sound (where the file says so, a hundred), at the witness's
            own statistic."""
            params, rows, program, model = witnessed
            for name, value in case.witness.pieces.items():
                monkeypatch.setattr(reference, name, value)
            args = (program, params, batch_of(case, rows), model)
            moved = reference.logits_error(*args, faults=(fault,))
            assert moved > case.witness.floors.get(fault, 1e3) * tol
            more("fault", args, fault)

    if case.steps:
        @pytest.fixture(scope="module", name="trained")
        def trained_fixture(tmp_path_factory):
            return trained(case, tmp_path_factory.mktemp("monitor"))
        install(trained_fixture, "trained")

        def steps_test(trained):
            singly = trained.singly
            np.testing.assert_allclose(trained.scanned, singly, rtol=1e-5)
            assert singly[0] != singly[1]
            for a, b in zip(jax.tree.leaves(trained.after[-1]),
                            jax.tree.leaves(trained.params)):
                np.testing.assert_allclose(a, b, rtol=1e-4,
                                           atol=case.steps_atol)
            more("steps", trained)
        n = NUMBERS[case.steps]
        install(steps_test, "test_run_steps_over_%s_batches_equals_%s_steps"
                % (n, n))

    if case.counters is not None:
        @install
        def test_counters_and_gauges_only_under_a_monitor_session(
                request, tmp_path):
            """Off a session a call's observation reads nothing back and
            builds nothing (``observed`` holds that); under one the call
            wrote the model's readings."""
            if case.counters is True:
                seen = request.getfixturevalue("trained")
            else:
                tr, batches, losses, rows = observed(case, tmp_path, 8, 2,
                                                     **case.counters)
                seen = Trained(tr, batches, None, None, losses, None, rows)
            assert seen.scan._probe_fn is not None
            assert any(r["name"].startswith("monitor.train.")
                       for r in seen.rows)
            more("counters", seen)

    return out
