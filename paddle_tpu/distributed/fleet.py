"""Fleet — the unified distributed training facade.

Parity: incubate/fleet/base/fleet_base.py:38 (Fleet lifecycle:
init/is_first_worker/worker_num/init_worker/stop_worker),
incubate/fleet/collective/__init__.py:41 (Collective fleet;
DistributedStrategy :94 extending BuildStrategy; CollectiveOptimizer :325).

Engine translation: `fleet.distributed_optimizer(opt).minimize(loss)` tags
the program for data-parallel execution over the device mesh; Executor.run
with the fleet-compiled program shards the batch and psums gradients — the
collective transpiler's c_allreduce insertion (transpiler/collective.py:178)
is replaced by XLA's gradient all-reduce via shardings.  Multi-host init maps
the reference's gen_nccl_id bootstrap to jax.distributed.initialize.
"""

import os

from .role_maker import PaddleCloudRoleMaker
from ..compiler import BuildStrategy

__all__ = ["init", "is_first_worker", "worker_index", "worker_num",
           "is_worker", "is_server", "init_worker", "stop_worker",
           "distributed_optimizer", "DistributedStrategy", "fleet"]


class DistributedStrategy(BuildStrategy):
    """Parity: incubate/fleet/collective/__init__.py:94 — BuildStrategy plus
    fleet knobs."""

    def __init__(self):
        super().__init__()
        self.use_local_sgd = False
        self.local_sgd_steps = 1
        self.forward_recompute = False
        self.recompute_checkpoints = []
        self.use_amp = False
        self.amp_loss_scale = 1.0  # kept for API parity; bf16 needs no scaling
        self.nccl_comm_num = 1
        # PSLib parity: route beyond-HBM-budget embedding tables to the
        # host-RAM sparse service (paddle_tpu/hostps) instead of erroring at
        # the parallel/embedding.py capacity guard.  cache_slots sizes the
        # HBM hot-row cache each HostPSEmbedding gets from the router.
        self.use_host_sparse_table = False
        self.host_sparse_cache_slots = 0


class _Fleet:
    def __init__(self):
        self._role_maker = None
        self._initialized = False

    # -- lifecycle (fleet_base.py:38) -----------------------------------
    def init(self, role_maker=None):
        self._role_maker = role_maker or PaddleCloudRoleMaker()
        self._role_maker.generate_role()
        self._initialized = True
        self._maybe_init_multihost()
        return self

    def _maybe_init_multihost(self):
        """jax.distributed bootstrap from the PADDLE_* env contract (the
        c_gen_nccl_id / gen_nccl_id analogue, c_gen_nccl_id_op.cc:37)."""
        n = self._role_maker.worker_num()
        if n <= 1 or os.environ.get("PADDLE_TPU_SKIP_DIST_INIT"):
            return
        import jax

        if jax.distributed.is_initialized():
            return  # benign re-init (second fleet.init() in one process)
        eps = self._role_maker.get_trainer_endpoints()
        # a genuine bootstrap failure (bad coordinator address, port
        # conflict) must surface instead of degrading to inconsistent
        # single-process training — no exception swallowing here
        jax.distributed.initialize(
            coordinator_address=eps[0],
            num_processes=n,
            process_id=self._role_maker.worker_index(),
        )

    def is_first_worker(self):
        return self._role_maker.is_first_worker()

    def worker_index(self):
        return self._role_maker.worker_index()

    def worker_num(self):
        return self._role_maker.worker_num()

    def is_worker(self):
        return self._role_maker.is_worker()

    def is_server(self):
        return self._role_maker.is_server()

    def init_worker(self):
        pass

    def init_server(self, *args, **kwargs):
        pass

    def run_server(self):
        raise RuntimeError(
            "no parameter-server processes exist on the TPU runtime: PS "
            "modes are served by all-reduce DP (SURVEY.md §2.9); run every "
            "process as a worker")

    def stop_worker(self):
        pass

    def distributed_optimizer(self, optimizer, strategy=None):
        return DistributedOptimizer(optimizer, strategy or DistributedStrategy(),
                                    self)

    # -- checkpoint passthroughs (fleet_base.py save_*) ------------------
    def save_inference_model(self, *args, **kwargs):
        from .. import io

        return io.save_inference_model(*args, **kwargs)

    def save_persistables(self, exe, dirname, main_program=None):
        from .. import io

        return io.save_persistables(exe, dirname, main_program)


class DistributedOptimizer:
    """Parity: fleet_base.py:240 / collective CollectiveOptimizer :325.

    minimize() runs the base optimizer's minimize, then marks the program
    with the fleet strategy so Executor/CompiledProgram shard it over the
    mesh (the transpiler-pass replacement).
    """

    def __init__(self, optimizer, strategy, fleet_):
        self._optimizer = optimizer
        self._strategy = strategy
        self._fleet = fleet_
        # apply the routing knob NOW (table construction usually precedes
        # minimize) and AUTHORITATIVELY: the most recent strategy decides
        # whether beyond-budget vocabularies go to the host-RAM sparse
        # service or hit the loud capacity error
        from ..parallel import embedding as _embedding

        _embedding.enable_host_sparse_table(
            bool(getattr(strategy, "use_host_sparse_table", False)),
            cache_slots=getattr(strategy, "host_sparse_cache_slots", None))

    _warned_local_sgd = False

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        if (getattr(self._strategy, "use_local_sgd", False)
                and not DistributedOptimizer._warned_local_sgd):
            import warnings

            warnings.warn(
                "DistributedStrategy.use_local_sgd: the program-mode fleet "
                "path runs synchronous DP (per-step gradient all-reduce); "
                "real Local SGD (periodic replica averaging) lives in the "
                "functional engine — parallel/local_sgd.py "
                "make_local_sgd_train_step", stacklevel=2)
            DistributedOptimizer._warned_local_sgd = True
        if getattr(self._strategy, "forward_recompute", False):
            self._optimizer._use_remat = True
        result = self._optimizer.minimize(
            loss, startup_program=startup_program,
            parameter_list=parameter_list, no_grad_set=no_grad_set)
        program = loss.block.program
        program._fleet_strategy = self._strategy
        return result

    def __getattr__(self, item):
        return getattr(self._optimizer, item)


fleet = _Fleet()

# module-level convenience API (paddle.distributed.fleet style)
init = fleet.init
is_first_worker = fleet.is_first_worker
worker_index = fleet.worker_index
worker_num = fleet.worker_num
is_worker = fleet.is_worker
is_server = fleet.is_server
init_worker = fleet.init_worker
stop_worker = fleet.stop_worker
distributed_optimizer = fleet.distributed_optimizer
