"""Training backends: per-framework gang setup.

``Backend``/``BackendConfig`` mirror ``python/ray/train/backend.py:55,43``.
:class:`JaxConfig` is the TPU replacement for the torch process-group
rendezvous (``torch/config.py:69`` ``dist.init_process_group``):

- every rank joins a host-side collective group (gradient sync for
  plain data parallelism — the gloo-analog path that works anywhere), and
- with ``use_jax_distributed=True`` (real multi-host pods) rank 0
  publishes a coordinator address through the GCS KV and every worker
  calls ``jax.distributed.initialize`` so all hosts enter one SPMD
  program over ICI/DCN.  One worker per HOST, each owning all of its
  host's chips.  It is NOT a way to join several one-chip workers on one
  host: the runtime makes each of those an isolated 1x1x1 slice
  (``resource_spec.chip_env``), and libtpu would need the opposite
  description (one process grid over the host) to connect them.  For the
  chips of one host use one worker that holds them all and a mesh over
  its devices (``chip_smoke.py --chips 4``).  Tested on CPU processes
  only (``tests/test_multihost.py``); never run on a chip.
"""

from __future__ import annotations

import dataclasses
import uuid
from typing import Optional

import ray_tpu
from ray_tpu.train.worker_group import WorkerGroup


@dataclasses.dataclass
class BackendConfig:
    @property
    def backend_cls(self):
        return Backend


class Backend:
    def on_start(self, worker_group: WorkerGroup, backend_config: "BackendConfig"):
        pass

    def on_training_start(self, worker_group: WorkerGroup, backend_config: "BackendConfig"):
        pass

    def on_shutdown(self, worker_group: WorkerGroup, backend_config: "BackendConfig"):
        pass


@dataclasses.dataclass
class JaxConfig(BackendConfig):
    use_jax_distributed: bool = False
    coordinator_port: int = 0  # 0 = pick a free port
    group_name: Optional[str] = None  # collective group; default unique per run
    # extra env applied on every worker BEFORE jax initializes there
    # (XLA_FLAGS / JAX_PLATFORMS / TPU topology variables); the seat of
    # the reference torch config's backend env knobs
    env_vars: Optional[dict] = None

    @property
    def backend_cls(self):
        return _JaxBackend


class _JaxBackend(Backend):
    def on_start(self, worker_group: WorkerGroup, cfg: JaxConfig):
        n = worker_group.num_workers
        group = cfg.group_name or f"train-{uuid.uuid4().hex[:8]}"
        cfg.group_name = group
        # rank 0 first: it creates the coordinator the others poll for
        ray_tpu.get(
            worker_group.workers[0].join_collective_group.remote(n, 0, group),
            timeout=60,
        )
        ray_tpu.get(
            [
                w.join_collective_group.remote(n, i, group)
                for i, w in enumerate(worker_group.workers)
                if i > 0
            ],
            timeout=60,
        )
        env = {
            "RAY_TRAIN_WORLD_SIZE": str(n),
            "RAY_TRAIN_COLLECTIVE_GROUP": group,
        }
        if cfg.env_vars:
            env.update({k: str(v) for k, v in cfg.env_vars.items()})
        ray_tpu.get(
            [w.setup_env.remote({**env, "RAY_TRAIN_WORLD_RANK": str(i)})
             for i, w in enumerate(worker_group.workers)],
            timeout=60,
        )
        if cfg.use_jax_distributed:
            self._init_jax_distributed(worker_group, cfg)

    def _init_jax_distributed(self, worker_group: WorkerGroup, cfg: JaxConfig):
        """Multi-host SPMD bring-up (the `_setup_torch_process_group` seat)."""
        port = cfg.coordinator_port

        def get_coordinator(port):
            import socket

            host = socket.gethostbyname(socket.gethostname())
            if port == 0:
                s = socket.socket()
                s.bind(("", 0))
                port = s.getsockname()[1]
                s.close()
            return f"{host}:{port}"

        coordinator = worker_group.execute_single(0, get_coordinator, port)

        def init_dist(coordinator, num_processes, process_id):
            import jax

            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes,
                process_id=process_id,
            )
            return True

        import cloudpickle

        blob = cloudpickle.dumps(init_dist)
        ray_tpu.get(
            [w.execute.remote(blob, coordinator, worker_group.num_workers, i)
             for i, w in enumerate(worker_group.workers)],
            timeout=300,
        )

    def on_shutdown(self, worker_group: WorkerGroup, cfg: JaxConfig):
        pass
