"""Sharded training step: the TPU-native ParallelExecutor.

Parity surface: ParallelExecutor construction + Run
(parallel_executor.cc:393-628,708-725) and the BuildStrategy pass pipeline
(build_strategy.cc:59-230).  Where the reference builds an SSA op-handle
graph with AllReduce nodes and schedules it with thread pools, this builds
ONE jitted SPMD function: shard_map over the full (dp, pp, tp) mesh, local
jax.value_and_grad, explicit psum of gradients per the param sync spec
(the AllReduceOpHandle placement, details/all_reduce_op_handle.cc:48), and a
pure-pytree optimizer update.  Param broadcast at init (BCastParamsToDevices,
parallel_executor.cc:630-706) becomes jax.device_put with NamedShardings.
"""

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import collectives as col
from .mesh import local_shard_map
from ..monitor import devscope as _devscope, memscope as _memscope
from ..monitor.recompile import FIRST_CALL, compile_ledger

__all__ = ["TrainState", "RUNNING", "make_train_step", "StepTrainer",
           "shard_pytree", "stack_batches", "TrainLoop"]

# The key of a TrainState's third entry, where a model has one: running
# state, which a step hands on to the next and does not train (batch norm's
# running statistics; an average of the weights would be another).  It sorts
# after "params", so a TrainState flattens as (opt, params, running): with
# these leaves ahead of the others the chip's compiler scheduled one more
# copy into ResNet's one-step program.
RUNNING = "running"


class TrainState(dict):
    """{'params': pytree, 'opt': pytree} and, for a model with running
    state, {RUNNING: pytree} beside them — kept a plain dict so it is a
    pytree (the Scope-of-persistables analogue, scope.h:46)."""

    @staticmethod
    def create(params, optimizer):
        with compile_ledger().phase("init_opt_state"):
            opt = jax.block_until_ready(optimizer[0](params))
        return {"params": params, "opt": opt}


def _opt_state_specs(param_specs, opt_state):
    """Sharding specs for optimizer state: moment-like leaves mirror their
    param's spec (so opt state shards with params — kReduce/ZeRO-adjacent,
    build_strategy.h:58); scalars are replicated."""
    p_struct = jax.tree.structure(param_specs)
    out = {}
    for k, v in opt_state.items():
        if jax.tree.structure(v) == p_struct:
            out[k] = param_specs
        else:
            out[k] = jax.tree.map(lambda _: P(), v)
    return out


def state_specs(param_specs, state):
    """Sharding specs of a TrainState; running state is replicated."""
    specs = {"params": param_specs,
             "opt": _opt_state_specs(param_specs, state["opt"])}
    if RUNNING in state:
        specs[RUNNING] = jax.tree.map(lambda _: P(), state[RUNNING])
    return specs


def shard_pytree(tree, specs, mesh):
    """Place a host pytree onto the mesh per spec (BCastParamsToDevices
    parity, parallel_executor.cc:630 — XLA shards/replicates instead of
    ncclBcast loops)."""
    with compile_ledger().phase("place", bytes=_tree_bytes(tree)):
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            tree, specs)


def _tree_bytes(tree):
    return sum(getattr(x, "nbytes", 0) for x in jax.tree.leaves(tree))


def make_train_step(loss_fn, mesh, param_specs, grad_syncs, optimizer,
                    batch_specs, donate=True, stepped=()):
    """Build the jitted sharded train step: the one builder every
    ``build_*_trainer`` goes through.  It decides where the gradients are
    summed, what is donated, how N steps become one dispatch and, through
    ``StepTrainer``, how the programs are named for a trace.

    loss_fn is per-device shard_map code whose final loss is already
    globally reduced (replicated), in one of two shapes, told apart by the
    state template ``build(state)`` is handed, not by an argument:

    - ``loss_fn(params_local, batch_local) -> loss`` where the state is
      ``{params, opt}`` (BERT, OLMoE);
    - ``loss_fn(params_local, running, batch_local) -> (loss, new_running)``
      where the state holds ``RUNNING`` as well (ResNet's running
      statistics).  Running state is replicated over the whole mesh, gets
      no gradient and no optimizer slot, and is donated and scanned with the
      rest of the state.  What it means across ``dp`` is the model's to
      say: the loss function returns it already the same on every shard
      (ResNet takes the ``pmean`` of its batch statistics under the
      ``grad_sync`` scope); the builder reduces nothing of it.

    ``stepped``: names of top-level leaves of ``params`` that a STEP sets
    and the optimizer does not (a router's selection biases, which the
    load moves): ``loss_fn(params_local, batch_local) -> (loss, {name: next
    value})``, the same on every shard.  They stay in ``params``, where
    whoever reads the model finds them (a checkpoint, an export, a
    reference), and not under ``RUNNING``; whatever the optimizer made of
    their zero gradient and its weight decay is dropped.

    grad_syncs: pytree (matching params) of tuples of mesh axis names whose
    partial gradients must be psum'd (transformer.grad_sync_axes).
    batch_specs: pytree of PartitionSpec for the batch dict.
    Returns build(state) -> step(state, batch, lr) -> (state, loss), and
    build.multi(state) for the scan over staged batches.
    """
    _, opt_update = optimizer

    def _sync_grad(g, axes):
        for a in axes:
            g = col.psum(g, a)
        return g

    def device_step(state, batch, lr):
        params = state["params"]
        new_state = {}
        if RUNNING in state:
            (loss, new_state[RUNNING]), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, state[RUNNING], batch)
        elif stepped:
            (loss, own), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            assert set(own) == set(stepped), (sorted(own), stepped)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        flat_g, treedef = jax.tree.flatten(grads)
        flat_s = treedef.flatten_up_to(grad_syncs)
        with jax.named_scope(_devscope.GRAD_SYNC):
            flat_g = [_sync_grad(g, axes) for g, axes in zip(flat_g, flat_s)]
        grads = jax.tree.unflatten(treedef, flat_g)
        with jax.named_scope(_devscope.OPTIMIZER):
            new_state["params"], new_state["opt"] = opt_update(
                grads, state["opt"], params, lr)
        if stepped:
            new_state["params"] = dict(new_state["params"], **own)
        return new_state, loss

    def _mapped(state_template):
        """The shard_map'ed per-step function — single source of the
        in/out specs for both the one-step and scanned entries."""
        sspecs = state_specs(param_specs, state_template)
        return local_shard_map(
            device_step, mesh,
            in_specs=(sspecs, batch_specs, P()),
            out_specs=(sspecs, P()),
        )

    def build(state_template):
        return jax.jit(_mapped(state_template),
                       donate_argnums=(0,) if donate else ())

    def build_multi(state_template):
        """Device-side training loop: ONE dispatch runs N steps via lax.scan
        over pre-staged batches (leaves [N, ...batch_shape]).  The MultiTrainer
        analogue (trainer.h:64 — N iterations per Run call): host dispatch and
        feed latency amortize across the whole scan instead of costing one
        round-trip per step.  Returns multi(state, batches, lr) ->
        (state, losses[N])."""
        mapped = _mapped(state_template)

        def multi(state, batches, lr):
            return jax.lax.scan(lambda st, b: mapped(st, b, lr), state, batches)

        return jax.jit(multi, donate_argnums=(0,) if donate else ())

    build.multi = build_multi
    return build


@dataclasses.dataclass
class StepTrainer:
    """What all three ``build_*_trainer`` (BERT's, the causal decoders' of
    ``parallel/decoder.py``, ResNet's) return: the state on the mesh
    (``params``, ``opt`` and, for ResNet, ``RUNNING``), the jitted step of
    ``make_train_step`` and its scan.  A model's trainer names its programs
    (``label``) and may count what a call is about to do (``_observe``,
    under a monitor session only)."""

    cfg: object
    mesh: object
    state: dict
    step_fn: object
    specs: dict
    multi_fn: object = None
    label = "train"
    # which of the two programs monitor.devscope has been told of
    _step_seen = _multi_seen = False

    def __post_init__(self):
        # MemScope owners (weakly; the state is donated every step, so the
        # walk reads the trainer's current one): params, opt_state, running
        _memscope.track_state(self, lambda tr: tr.state)

    def _observe(self, batch):
        """``batch`` as ``step`` or ``run_steps`` got it (the latter's with a
        leading step axis).  The rule for what a trainer writes here: a name
        under ``monitor.train.*`` is a reading that depends on the batch's
        data or on the weights; what the configuration and the shapes fix
        is a function, and its test calls the function."""

    def step(self, batch, lr):
        ledger, program = compile_ledger(), self.label + ".step"
        if self._step_seen:
            with ledger.call(program):
                self._observe(batch)
                self.state, loss = self.step_fn(self.state, batch, lr)
            return loss
        # the call that traces, lowers and compiles or loads the program (a
        # probe that ``_observe`` compiles is not the phase's)
        self._observe(batch)
        with ledger.phase(FIRST_CALL, program=program), ledger.call(program):
            self._step_seen = _devscope.register(
                program, self.step_fn, (self.state, batch, lr))
            self.state, loss = self.step_fn(self.state, batch, lr)
        return loss

    def run_steps(self, batches, lr):
        """Run N steps in one dispatch (device-side lax.scan loop —
        make_train_step build_multi).  batches: pytree with leading [N] step
        axis, already staged via stack_batches.  Returns losses [N]."""
        if self.multi_fn is None:
            raise RuntimeError("trainer built without multi-step support")
        ledger, program = compile_ledger(), self.label + ".run_steps"
        if self._multi_seen:
            with ledger.call(program):
                self._observe(batches)
                self.state, losses = self.multi_fn(self.state, batches, lr)
            return losses
        self._observe(batches)
        with ledger.phase(FIRST_CALL, program=program), ledger.call(program):
            self._multi_seen = _devscope.register(
                program, self.multi_fn, (self.state, batches, lr))
            # whoever staged them (``stack_batches``, or a caller's own
            # program on the device): what a scan runs over is staged
            _memscope.track_arrays("staged_batches", batches)
            self.state, losses = self.multi_fn(self.state, batches, lr)
        return losses


class TrainLoop:
    """Fault-tolerant host-side step loop over a jitted step function and a
    pytree state — CheckpointPolicy coverage for the training entry points
    that do NOT go through ``Executor.train_from_dataset`` (raw
    ``make_train_step`` loops, the bench long-run mode).

    Contract (same as the trainer-side guard, ft/guard.py):

    - ``checkpoint=ft.CheckpointPolicy(...)`` turns on boundary saves (the
      async shard/COMMIT protocol of parallel/checkpoint.py), resume, and
      SIGTERM handling — including the multi-rank agreed-boundary
      preemption protocol, so a fleet of step loops stages ONE agreed
      ``ckpt-<step>`` on preemption;
    - the in-flight window (feed_pipe.InFlightWindow, if the caller uses
      one) is DRAINED before every snapshot — no donated buffer mid-flight;
    - ``resume=True`` restores the latest committed state and fast-forwards
      the batch stream by CONSUMING the already-trained prefix (the stream
      replays deterministically from its seed, so skipped draws keep host
      RNG state exactly where the uninterrupted run would have it).

    Usage::

        loop = TrainLoop(step_fn, checkpoint=policy, window=window)
        state, steps = loop.run(state, batches)

    ``step_fn(state, batch) -> (state, aux)``; aux is admitted into the
    window (bounded async dispatch) when one is given.
    """

    def __init__(self, step_fn, checkpoint=None, window=None,
                 on_step=None, sentinel=None):
        self.step_fn = step_fn
        self.window = window
        self.on_step = on_step
        # model-health watcher (monitor/sentinel.py): None = the active
        # session's sentinel (if any); False = off for this loop.  The loop
        # feeds it the SAMPLED aux — loss gauges, divergence detectors
        # (loss-spike z-score / plateau), and the nonfinite-loss tripwire
        # (halt raises; the skip policies cannot un-apply an already-
        # donated pytree update, so here they count and continue).
        self._sentinel = sentinel
        self._guard = None
        if checkpoint is not None:
            from ..ft.guard import LoopGuard

            self._guard = LoopGuard(checkpoint, self._current_state,
                                    drain=self._drain)
        self._state = None
        self.last_aux = None
        self.resumed_step = 0
        # MemScope owner registration (weakref — dies with the loop): a
        # TrainState's parts classify as the trainers' do (params,
        # opt_state, running), any other pytree whole as "train_state"
        _memscope.track_state(self, lambda lp: lp._state)

    def _current_state(self):
        return self._state

    def _drain(self):
        if self.window is not None:
            self.window.drain()

    @property
    def guard(self):
        return self._guard

    def run(self, state, batches):
        """Drive `batches` through the step function.  Returns
        (final_state, steps_trained_total) — steps include the fast-forward
        prefix on resume, so the count matches the uninterrupted run's."""
        self._state = state
        step = 0
        sent = self._sentinel
        if sent is None:
            from ..monitor import sentinel as _sentinel_mod

            sent = _sentinel_mod.active_sentinel()
        elif sent is False:
            sent = None
        if sent is not None:
            sent.on_run_start()
        if self._guard is not None:
            self._state, step = self._guard.maybe_resume(state)
            self.resumed_step = step
            self._guard.install_signal()
        try:
            skip = step
            for k, batch in enumerate(batches):
                if k < skip:
                    continue      # consumed, not trained: exact-batch resume
                self._state, self.last_aux = self.step_fn(self._state, batch)
                if self.window is not None:
                    self.window.admit(self.last_aux)
                step = k + 1
                if self.on_step is not None:
                    self.on_step(step, self.last_aux)
                if sent is not None:
                    # sampled: materializing aux is a sync, paid every
                    # sentinel.sample_every-th step only
                    sent.observe_loop(step, self.last_aux)
                if self._guard is not None:
                    self._guard.after_step(step)
            self._drain()
            if self._guard is not None:
                self._guard.finish()
        except BaseException as e:
            # MemScope OOM postmortem for raw step loops: a
            # RESOURCE_EXHAUSTED surfacing here (dispatch or the drain's
            # deferred XLA error) dumps the flight record with the memory
            # section — dedup makes a later excepthook dump a no-op
            if not isinstance(e, SystemExit) \
                    and _memscope.is_resource_exhausted(e):
                from ..monitor import session as _session

                mon = _session.active()
                if mon is not None:
                    _memscope.note_oom(mon, None, e)
            raise
        finally:
            if self._guard is not None:
                self._guard.restore_signal()
        return self._state, step


def stack_batches(mesh, batch_specs, batches):
    """Stack a list of host batch dicts along a new leading step axis and
    place them on the mesh (step axis replicated, batch dims per spec)."""
    import numpy as np

    with compile_ledger().phase("stage_batches") as labels:
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *batches)
        specs = jax.tree.map(lambda s: P(None, *tuple(s)), batch_specs,
                             is_leaf=lambda x: isinstance(x, P))
        labels["bytes"] = _tree_bytes(stacked)
        staged = shard_pytree(stacked, specs, mesh)
        _memscope.track_arrays("staged_batches", staged)
        return staged
