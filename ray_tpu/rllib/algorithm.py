"""Algorithm / AlgorithmConfig: the RLlib training driver.

Analog of ``/root/reference/rllib/algorithms/algorithm.py:142`` (Algorithm
— a Tune Trainable whose ``step`` runs ``training_step`` and aggregates
rollout metrics) and ``algorithm_config.py:112`` (the fluent builder).
An Algorithm owns a WorkerSet; subclasses implement ``training_step()``
(sample → SGD → sync), the reference's ``algorithm.py:1284`` seam.
"""

from __future__ import annotations

import copy
from typing import Any, Dict, Optional, Type

import numpy as np

from ray_tpu.rllib.sample_batch import SampleBatch
from ray_tpu.rllib.worker_set import WorkerSet
from ray_tpu.tune.trainable import Trainable


class AlgorithmConfig:
    """Fluent config builder (``algorithm_config.py:112`` analog)."""

    def __init__(self, algo_class: Optional[Type["Algorithm"]] = None):
        self.algo_class = algo_class
        self._config: Dict[str, Any] = {
            "env": None,
            "env_creator": None,
            "env_config": {},
            "num_rollout_workers": 0,
            "num_cpus_per_worker": 1,
            "rollout_fragment_length": 200,
            "num_envs_per_worker": 1,
            "train_batch_size": 4000,
            "evaluation_interval": 0,  # 0 = never
            "evaluation_num_episodes": 5,
            "input": None,
            "output": None,
            "gamma": 0.99,
            "lr": 5e-4,
            "fcnet_hiddens": (64, 64),
            "seed": 0,
            "framework": "jax",
            # env<->policy transform pipelines (rllib/connectors); None =
            # defaults derived from the spaces.  "observation_filter"
            # appends running-stat normalization to the default pipeline
            # (the reference's MeanStdFilter config knob).
            "agent_connectors": None,
            "action_connectors": None,
            "observation_filter": None,
            # RLModule plugin: factory(ConnectorContext) -> RLModule
            "_rl_module_factory": None,
        }

    # -- fluent sections (reference section names) ---------------------
    def environment(self, env: Optional[str] = None, *, env_creator=None,
                    env_config: Optional[Dict] = None) -> "AlgorithmConfig":
        if env is not None:
            self._config["env"] = env
        if env_creator is not None:
            self._config["env_creator"] = env_creator
        if env_config is not None:
            self._config["env_config"] = env_config
        return self

    def rollouts(self, *, num_rollout_workers: Optional[int] = None,
                 rollout_fragment_length: Optional[int] = None,
                 num_envs_per_worker: Optional[int] = None) -> "AlgorithmConfig":
        if num_rollout_workers is not None:
            self._config["num_rollout_workers"] = num_rollout_workers
        if rollout_fragment_length is not None:
            self._config["rollout_fragment_length"] = rollout_fragment_length
        if num_envs_per_worker is not None:
            self._config["num_envs_per_worker"] = num_envs_per_worker
        return self

    def training(self, **kwargs) -> "AlgorithmConfig":
        self._config.update(kwargs)
        return self

    def resources(self, *, num_cpus_per_worker: Optional[int] = None) -> "AlgorithmConfig":
        if num_cpus_per_worker is not None:
            self._config["num_cpus_per_worker"] = num_cpus_per_worker
        return self

    def framework(self, framework: str = "jax") -> "AlgorithmConfig":
        if framework != "jax":
            raise ValueError("only framework='jax' is supported")
        return self

    def connectors(self, *, agent_connectors=None, action_connectors=None,
                   observation_filter: Optional[str] = None
                   ) -> "AlgorithmConfig":
        """Compose the env<->policy transform pipelines.

        ``agent_connectors``/``action_connectors`` accept a list of
        connector instances, ``(name, kwargs)`` pairs, or a factory
        ``fn(ctx) -> connectors``; ``observation_filter="MeanStdFilter"``
        appends running-stat normalization to the default pipeline."""
        if agent_connectors is not None:
            self._config["agent_connectors"] = agent_connectors
        if action_connectors is not None:
            self._config["action_connectors"] = action_connectors
        if observation_filter is not None:
            self._config["observation_filter"] = observation_filter
        return self

    def rl_module(self, module_factory) -> "AlgorithmConfig":
        """Plug a custom model in WITHOUT subclassing Policy:
        ``module_factory(ctx: ConnectorContext) -> RLModule`` builds the
        network every policy (rollout workers, learner, PolicyServer)
        routes its forwards through."""
        self._config["_rl_module_factory"] = module_factory
        return self

    def evaluation(self, *, evaluation_interval: Optional[int] = None,
                   evaluation_num_episodes: Optional[int] = None) -> "AlgorithmConfig":
        if evaluation_interval is not None:
            self._config["evaluation_interval"] = evaluation_interval
        if evaluation_num_episodes is not None:
            self._config["evaluation_num_episodes"] = evaluation_num_episodes
        return self

    def offline_data(self, *, input_: Optional[str] = None,
                     output: Optional[str] = None) -> "AlgorithmConfig":
        """Offline IO (``rllib/offline`` analog): ``output`` makes every
        rollout worker write its fragments as JSON lines; ``input_`` trains
        replay-based algorithms from recorded batches instead of an env."""
        if input_ is not None:
            self._config["input"] = input_
        if output is not None:
            self._config["output"] = output
        return self

    def debugging(self, *, seed: Optional[int] = None) -> "AlgorithmConfig":
        if seed is not None:
            self._config["seed"] = seed
        return self

    # -- materialize ---------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        d = copy.copy(self._config)
        d["_algo_class"] = self.algo_class
        return d

    def build(self) -> "Algorithm":
        if self.algo_class is None:
            raise ValueError("config has no algo_class; use e.g. PPOConfig()")
        return self.algo_class(config=self.to_dict())


class Algorithm(Trainable):
    """Tune-trainable RL driver (``algorithm.py:142``)."""

    _default_config: Dict[str, Any] = {}

    def __init__(self, config: Optional[Any] = None, **kwargs):
        if isinstance(config, AlgorithmConfig):
            config = config.to_dict()
        super().__init__(config, **kwargs)

    # -- Trainable hooks -----------------------------------------------
    def setup(self, config: Dict[str, Any]) -> None:
        from ray_tpu._private.usage import record_feature
        record_feature("rllib")
        merged = dict(self._default_config)
        merged.update({k: v for k, v in config.items() if k != "_algo_class"})
        self.config = merged
        self.workers = WorkerSet(merged)
        self._timesteps_total = 0
        self._iteration_count = 0
        self.reader = None
        if merged.get("input"):
            from ray_tpu.rllib.offline import JsonReader

            self.reader = JsonReader(merged["input"])

    def step(self) -> Dict[str, Any]:
        results = self.training_step()
        self._iteration_count += 1
        metrics = (
            self.workers.collect_metrics()
            + [self.workers.local_worker.get_metrics()]
            if self.workers.remote_workers
            else [self.workers.local_worker.get_metrics()]
        )
        rews = [m["episode_reward_mean"] for m in metrics
                if not np.isnan(m["episode_reward_mean"])]
        lens = [m["episode_len_mean"] for m in metrics
                if not np.isnan(m["episode_len_mean"])]
        results.update({
            "episode_reward_mean": float(np.mean(rews)) if rews else np.nan,
            "episode_len_mean": float(np.mean(lens)) if lens else np.nan,
            "episodes_total": int(sum(m["episodes_total"] for m in metrics)),
            "timesteps_total": self._timesteps_total,
        })
        interval = self.config.get("evaluation_interval") or 0
        if interval and self._iteration_count % interval == 0:
            results["evaluation"] = self.evaluate()
        return results

    def evaluate(self) -> Dict[str, Any]:
        """Greedy episodes on a fresh env (``Algorithm.evaluate`` analog)."""
        return self.workers.local_worker.evaluate_episodes(
            int(self.config.get("evaluation_num_episodes", 5))
        )

    def _read_offline(self, min_env_steps: int) -> SampleBatch:
        """Accumulate recorded batches from ``config.input`` to at least
        ``min_env_steps`` transitions (offline-training sampling seam)."""
        parts, total = [], 0
        while total < min_env_steps:
            b = self.reader.next()
            if b.count == 0:
                continue
            parts.append(b)
            total += b.count
        return SampleBatch.concat_samples(parts)

    def training_step(self) -> Dict[str, Any]:
        """Default: sample and do nothing (``algorithm.py:1284`` is
        framework-specific; subclasses override)."""
        batch = self.workers.synchronous_parallel_sample()
        self.workers.sync_filters()
        self._timesteps_total += batch.count
        return {}

    def cleanup(self) -> None:
        self.workers.stop()

    # -- checkpointing (Trainable currency) ----------------------------
    def save_checkpoint(self) -> Dict:
        worker = self.workers.local_worker
        state = {
            "policy_state": worker.policy.get_state(),
            "timesteps_total": self._timesteps_total,
            "config": {k: v for k, v in self.config.items()
                       if isinstance(v, (int, float, str, bool, tuple, list, dict, type(None)))},
        }
        # connector pipelines (running-stat filters etc.) ride checkpoints
        getter = getattr(worker, "get_connector_state", None)
        if getter is not None:
            state["connector_state"] = getter()
        return state

    def load_checkpoint(self, state: Dict) -> None:
        if "policy_state" in state:
            self.workers.local_worker.policy.set_state(state["policy_state"])
        else:  # older checkpoints carried bare weights
            self.workers.local_worker.set_weights(state["weights"])
        if state.get("connector_state") is not None:
            self.workers.local_worker.set_connector_state(
                state["connector_state"])
            self.workers.sync_connectors()
        self._timesteps_total = state.get("timesteps_total", 0)
        self.workers.sync_weights()

    # -- inference ------------------------------------------------------
    def compute_single_action(self, obs, explore: bool = False,
                              episode_start: bool = False) -> int:
        """Greedy (or sampled) action for one observation.

        Stateful connectors (frame stacks) track the caller's episode on
        the shared eval stream: pass ``episode_start=True`` on the first
        observation of each new episode so their state resets with it."""
        worker = self.workers.local_worker
        policy = worker.policy
        if episode_start:
            from ray_tpu.rllib.rollout_worker import EVAL_ENV_ID

            worker.agent_connectors.reset(EVAL_ENV_ID)
        # the same pipeline as sampling (eval stream: frozen statistics)
        obs = worker._prep_obs(obs)[None]
        if explore:
            action, _, _ = policy.compute_actions(obs)
            return int(action[0])
        # greedy through the policy's RLModule forward_inference path
        return int(np.asarray(policy.greedy_action(obs))[0])

    def get_policy(self):
        return self.workers.local_worker.policy


# -- execution ops (rollout_ops/train_ops analogs as free functions) -----

def synchronous_parallel_sample(worker_set: WorkerSet, *, max_env_steps: int) -> SampleBatch:
    """Sample rounds until at least ``max_env_steps`` are collected
    (``execution/rollout_ops.py:21``)."""
    batches = []
    total = 0
    while total < max_env_steps:
        b = worker_set.synchronous_parallel_sample()
        batches.append(b)
        total += b.count
    # remote workers' running-stat filters (MeanStdFilter) fold into the
    # learner's pipelines once per sampling round; no-op without stats
    worker_set.sync_filters()
    return SampleBatch.concat_samples(batches)


def train_one_step(
    policy,
    batch: SampleBatch,
    *,
    num_sgd_iter: int,
    sgd_minibatch_size: int,
    rng: np.random.Generator,
    required_keys: tuple,
) -> Dict[str, float]:
    """Minibatch SGD epochs over one train batch
    (``execution/train_ops.py:26``)."""
    import time

    from ray_tpu._private import events

    t_wall = time.perf_counter()
    if hasattr(policy, "train_on_batch"):
        # server-resident learner (policy_server.py): the batch crosses
        # the wire once and every SGD update runs device-side, with no
        # readback between minibatches
        out = policy.train_on_batch(
            batch, num_sgd_iter=num_sgd_iter,
            sgd_minibatch_size=sgd_minibatch_size,
            required_keys=required_keys, seed=int(rng.integers(1 << 31)))
        events.emit("rllib", "learner train", entity_id="learner",
                    span_dur=time.perf_counter() - t_wall,
                    env_steps=batch.count, server_side=True)
        return out
    metrics: Dict[str, float] = {}
    count = 0
    mb_size = min(sgd_minibatch_size, batch.count)
    for _ in range(num_sgd_iter):
        for mb in batch.minibatches(mb_size, rng):
            out = policy.learn_on_minibatch(
                {k: mb[k] for k in required_keys}
            )
            for k, v in out.items():
                metrics[k] = metrics.get(k, 0.0) + v
            count += 1
    events.emit("rllib", "learner train", entity_id="learner",
                span_dur=time.perf_counter() - t_wall,
                env_steps=batch.count, sgd_minibatches=count)
    return {k: v / max(count, 1) for k, v in metrics.items()}
