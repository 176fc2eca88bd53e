"""Chip-resident policy service: batched inference + learner in one actor.

The reference scales Atari PPO by running policy inference inside each
CPU rollout worker and shipping gradients/weights around
(``/root/reference/rllib/evaluation/rollout_worker.py:153``,
``rllib/execution/train_ops.py:26``).  On TPU that shape is wrong twice
over: CPU conv inference starves the chip, and a chip belongs to one
process, so it cannot be shared among rollout workers anyway.  Here ONE
actor owns the chip and exposes the whole policy surface:

- ``compute_actions`` — rollout workers ship uint8 observation batches
  and get (actions, logp, vf) back; concurrent worker calls pipeline on
  the device (the actor runs threaded; readbacks overlap dispatch).
- ``train_on_batch`` — the learner: one batch ships once, every SGD
  minibatch update runs device-side with no intermediate readbacks.

Rollout workers plug in through :class:`RemotePolicy`, which implements
the JaxPolicy calling convention over an actor handle, so RolloutWorker,
the algorithms, and checkpointing are unchanged (``_policy_class`` seam).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ray_tpu._private import events


class PolicyServer:
    """Actor hosting the real JaxPolicy (build with ``num_tpus=1`` and
    ``max_concurrency > num_rollout_workers`` so worker inference calls
    overlap on the device)."""

    def __init__(self, obs_dim: int, num_actions: int,
                 policy_kwargs: Optional[Dict[str, Any]] = None,
                 algo_config: Optional[Dict[str, Any]] = None):
        from ray_tpu.rllib.policy import JaxPolicy

        kwargs = dict(policy_kwargs or {})
        if algo_config is not None:
            # mirror RolloutWorker's policy construction from a config
            factory = algo_config.get("_loss_factory")
            if factory is not None and "loss_fn" not in kwargs:
                kwargs["loss_fn"] = factory(algo_config)
            kwargs.setdefault("lr", algo_config.get("lr", 5e-4))
            kwargs.setdefault(
                "hiddens", tuple(algo_config.get("fcnet_hiddens", (64, 64))))
            kwargs.setdefault("grad_clip", algo_config.get("grad_clip", 0.5))
            kwargs.setdefault("seed", int(algo_config.get("seed") or 0))
            module_factory = algo_config.get("_rl_module_factory")
            if module_factory is not None and "module" not in kwargs:
                # same RLModule plugin seam as RolloutWorker: the server-
                # resident policy routes its forwards through the module
                from ray_tpu.rllib.connectors import ConnectorContext

                obs_shape = tuple(kwargs.get("obs_shape") or (obs_dim,))
                kwargs["module"] = module_factory(ConnectorContext(
                    obs_shape=obs_shape, obs_dim=obs_dim,
                    num_actions=num_actions, config=dict(algo_config)))
        self.policy = JaxPolicy(obs_dim, num_actions, **kwargs)
        # serializes rng splits and param updates; device dispatch happens
        # inside, readbacks outside, so concurrent callers overlap the
        # expensive part (host<->device transit)
        self._lock = threading.Lock()
        self._weights_version = 0
        # frame-stack transport (shipping full 4-channel stacks every tick
        # — 3 of whose channels the device already holds — moves 4x the
        # bytes over the actor call and the host->device copy):
        # per-worker device-resident stacked observations, advanced from
        # single new frames; snapshots cached device-side so training
        # never re-ships pixels at all
        self._rollouts: Dict[int, Dict[str, Any]] = {}
        # insertion-ordered (python dict): eviction is FIFO = oldest first
        self._obs_cache: Dict[Tuple[int, int], Any] = {}
        self._obs_cache_bytes = 0
        # backstop if training never consumes the cache; sized in bytes so
        # n_envs doesn't change the memory envelope
        self._obs_cache_cap_bytes = 2 << 30
        self._advance_jit = None
        self._update_cached_jit = None

    def describe(self) -> Dict[str, Any]:
        return {
            "has_conv": "conv" in self.policy.params,
            "weights_version": self._weights_version,
        }

    # -- inference ------------------------------------------------------
    def compute_actions(self, obs: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        p = self.policy
        with self._lock:
            p._rng, key = jax.random.split(p._rng)
            a, lp, v = p._sample_jit(p.params, key, jnp.asarray(obs))
            for x in (a, lp, v):
                x.copy_to_host_async()
        out = np.asarray(a), np.asarray(lp), np.asarray(v)
        # server-side compute span: a rollout worker's infer_s minus the
        # sum of these is the transport share of its inference wait
        events.emit("rllib", "policy inference", entity_id="policy-server",
                    span_dur=time.perf_counter() - t0, batch=len(out[0]))
        return out

    # -- frame-stack transport -----------------------------------------
    def start_rollout(self, worker_id: int, n_envs: int) -> bool:
        """(Re)initialize a worker's device-resident stacked observation
        state; clears its cached snapshots (worker restart path)."""
        with self._lock:
            self._rollouts[worker_id] = {"state": None, "n_envs": n_envs,
                                         "tick": -1}
            self._obs_cache = {
                k: v for k, v in self._obs_cache.items() if k[0] != worker_id
            }
        return True

    def _build_advance(self):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def advance(state, new_frames, reset_mask):
            # state [n, H, W, C] uint8; new_frames [n, H, W]; reset rows
            # become C copies of the fresh frame (the DeepMind frame-stack
            # reset semantic); live rows roll and append
            rolled = jnp.concatenate(
                [state[..., 1:], new_frames[..., None]], axis=-1)
            stacked = jnp.repeat(
                new_frames[..., None], state.shape[-1], axis=-1)
            return jnp.where(
                reset_mask[:, None, None, None], stacked, rolled)

        return advance

    def compute_actions_stacked(self, worker_id: int, new_frames: np.ndarray,
                                reset_mask: np.ndarray):
        """One rollout tick shipping ONLY each env's newest frame
        [n, H, W] uint8 (+ reset mask); the device rolls its resident
        stacks, runs the policy, and snapshots the stacks for training.
        Returns (actions, logp, vf, tick) — obs references (worker, tick,
        env) stand in for pixels in the sample batch."""
        import jax
        import jax.numpy as jnp

        t_start = time.perf_counter()
        p = self.policy
        with self._lock:
            ro = self._rollouts.get(worker_id)
            if ro is None:
                ro = self._rollouts[worker_id] = {
                    "state": None, "n_envs": len(new_frames), "tick": -1}
            if self._advance_jit is None:
                self._advance_jit = self._build_advance()
            if ro["state"] is None:
                n, h, w = new_frames.shape
                c = 4
                ro["state"] = jnp.zeros((n, h, w, c), jnp.uint8)
            ro["state"] = self._advance_jit(
                ro["state"], jnp.asarray(new_frames),
                jnp.asarray(reset_mask.astype(bool)))
            ro["tick"] += 1
            tick = ro["tick"]
            self._obs_cache[(worker_id, tick)] = ro["state"]
            self._obs_cache_bytes += int(np.prod(ro["state"].shape))
            while (self._obs_cache_bytes > self._obs_cache_cap_bytes
                   and len(self._obs_cache) > 1):
                oldest = next(iter(self._obs_cache))  # FIFO: oldest insert
                self._obs_cache_bytes -= int(
                    np.prod(self._obs_cache.pop(oldest).shape))
            p._rng, key = jax.random.split(p._rng)
            a, lp, v = p._sample_jit(p.params, key, ro["state"])
            for x in (a, lp, v):
                x.copy_to_host_async()
        events.emit("rllib", "policy inference", entity_id="policy-server",
                    span_dur=time.perf_counter() - t_start,
                    batch=len(new_frames), stacked=True)
        return np.asarray(a), np.asarray(lp), np.asarray(v), tick

    def peek_obs(self, worker_id: int) -> Optional[np.ndarray]:
        """Current device-resident stacks for a worker (tests/debugging)."""
        with self._lock:
            ro = self._rollouts.get(worker_id)
            if ro is None or ro["state"] is None:
                return None
            return np.asarray(ro["state"])

    def value(self, obs: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp

        t0 = time.perf_counter()
        with self._lock:
            v = self.policy._value_jit(self.policy.params, jnp.asarray(obs))
        out = np.asarray(v)
        # bootstrap value calls count into the workers' infer_s; without
        # this span their server-side compute would read as "transport"
        # in the scaling-knee attribution
        events.emit("rllib", "policy inference", entity_id="policy-server",
                    span_dur=time.perf_counter() - t0, batch=len(out))
        return out

    def greedy_action(self, obs: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp

        with self._lock:
            a = self.policy._greedy_jit(self.policy.params, jnp.asarray(obs))
        return np.asarray(a)

    def action_logp(self, obs: np.ndarray, actions: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp

        with self._lock:
            lp = self.policy._action_logp_jit(
                self.policy.params, jnp.asarray(obs), jnp.asarray(actions))
        return np.asarray(lp)

    # -- learning -------------------------------------------------------
    def train_on_batch(self, cols: Dict[str, np.ndarray], *,
                       num_sgd_iter: int, sgd_minibatch_size: int,
                       seed: int = 0) -> Dict[str, float]:
        """Minibatch SGD epochs entirely server-side: the batch crosses
        the wire once; each update is a single device dispatch (metrics
        read back once at the end).  An ``obs`` column of [N, 3] int32
        (worker, tick, env) references — the frame-stack transport path —
        is resolved against the device-resident snapshots instead:
        training then ships NO pixels at all."""
        obs = cols.get("obs")
        if (isinstance(obs, np.ndarray) and obs.ndim == 2
                and obs.shape[1] == 3
                and np.issubdtype(obs.dtype, np.integer)):
            # reference rows are unambiguous — an empty cache is an error
            # (evicted or purged), never a reason to train on coordinates
            return self._train_cached(
                cols, num_sgd_iter=num_sgd_iter,
                sgd_minibatch_size=sgd_minibatch_size, seed=seed)
        from ray_tpu.rllib.sample_batch import SampleBatch

        batch = SampleBatch(cols)
        rng = np.random.default_rng(seed)
        mb_size = min(sgd_minibatch_size, batch.count)
        metrics: Dict[str, float] = {}
        count = 0
        with self._lock:
            for _ in range(num_sgd_iter):
                for mb in batch.minibatches(mb_size, rng):
                    out = self.policy.learn_on_minibatch(dict(mb.items()))
                    for k, v in out.items():
                        metrics[k] = metrics.get(k, 0.0) + v
                    count += 1
            self._weights_version += 1
        return {k: v / max(count, 1) for k, v in metrics.items()}

    def _build_update_cached(self):
        import jax
        import optax

        loss_fn = self.policy._loss_fn
        optimizer = self.policy.optimizer

        @jax.jit
        def upd(params, opt_state, flat_obs, cols, idx):
            batch = {k: v[idx] for k, v in cols.items()}
            batch["obs"] = flat_obs[idx]  # device gather — no host pixels
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss, metrics

        return upd

    def _train_cached(self, cols: Dict[str, np.ndarray], *,
                      num_sgd_iter: int, sgd_minibatch_size: int,
                      seed: int) -> Dict[str, float]:
        import jax.numpy as jnp

        cols = dict(cols)
        refs = cols.pop("obs")
        with self._lock:
            # concatenate ONLY the snapshots this batch references — other
            # workers'/rounds' entries stay in cache, unmaterialized
            needed = sorted({(int(w), int(t)) for w, t, _ in refs})
            missing = [k for k in needed if k not in self._obs_cache]
            if missing:
                raise RuntimeError(
                    f"observation snapshots {missing[:3]} (of {len(missing)})"
                    " were evicted before training — raise the PolicyServer"
                    " obs cache cap or train sooner")
            offsets: Dict[Tuple[int, int], int] = {}
            arrs = []
            off = 0
            for k in needed:
                arr = self._obs_cache[k]
                offsets[k] = off
                off += arr.shape[0]
                arrs.append(arr)
            flat = jnp.concatenate(arrs, axis=0)
            row = np.array(
                [offsets[(int(w), int(t))] + int(e) for w, t, e in refs],
                np.int32)
            cols_dev = {k: jnp.asarray(v) for k, v in cols.items()}
            if self._update_cached_jit is None:
                self._update_cached_jit = self._build_update_cached()
            rng = np.random.default_rng(seed)
            n = len(row)
            mb = min(sgd_minibatch_size, n)
            params, opt_state = self.policy.params, self.policy.opt_state
            acc = None
            count = 0
            for _ in range(num_sgd_iter):
                perm = rng.permutation(n)
                for s in range(0, n - mb + 1, mb):
                    idx = jnp.asarray(row[perm[s:s + mb]])
                    params, opt_state, loss, m = self._update_cached_jit(
                        params, opt_state, flat, cols_dev, idx)
                    m = dict(m, total_loss=loss)
                    # accumulate ON DEVICE; one readback at the end
                    acc = m if acc is None else {
                        k: acc[k] + m[k] for k in m}
                    count += 1
            self.policy.params, self.policy.opt_state = params, opt_state
            self._weights_version += 1
            for k in needed:  # consumed; other entries await their batch
                self._obs_cache.pop(k, None)
            self._obs_cache_bytes = sum(
                int(np.prod(v.shape)) for v in self._obs_cache.values())
        names = sorted(acc)
        vals = np.asarray(jnp.stack([acc[k] for k in names]))
        return {k: float(v) / max(count, 1) for k, v in zip(names, vals)}

    # -- weights / state ------------------------------------------------
    def get_weights(self):
        with self._lock:
            return self.policy.get_weights()

    def set_weights(self, weights) -> int:
        with self._lock:
            self.policy.set_weights(weights)
            self._weights_version += 1
            return self._weights_version

    def get_state(self) -> Dict[str, Any]:
        with self._lock:
            return self.policy.get_state()

    def set_state(self, state: Dict[str, Any]) -> int:
        with self._lock:
            self.policy.set_state(state)
            self._weights_version += 1
            return self._weights_version


_SERVER_WEIGHTS_SENTINEL = "__policy_server_weights__"


class RemotePolicy:
    """JaxPolicy-shaped client over a PolicyServer handle.

    Accepts (and ignores) the local-policy construction kwargs so it drops
    into RolloutWorker through the ``_policy_class`` config seam.  Weight
    sync between workers becomes O(1): every worker's policy IS the same
    server, so ``get_weights`` returns a version token and ``set_weights``
    with a token is a no-op.
    """

    def __init__(self, obs_dim: int, num_actions: int, *, server=None,
                 timeout: float = 300.0, **_ignored):
        if server is None:
            raise ValueError(
                "RemotePolicy needs a PolicyServer actor handle: pass "
                "config['_policy_kwargs'] = {'server': handle}")
        self.obs_dim = obs_dim
        self.num_actions = num_actions
        self._server = server
        self._timeout = timeout
        import ray_tpu

        self._get = lambda ref: ray_tpu.get(ref, timeout=self._timeout)
        desc = self._get(server.describe.remote())
        # RolloutWorker sniffs `"conv" in policy.params` to keep image
        # observations [H, W, C]; mirror the server's architecture flag
        self.params: Dict[str, Any] = {"conv": True} if desc["has_conv"] else {}

    # -- acting ---------------------------------------------------------
    def compute_actions(self, obs):
        return self._get(self._server.compute_actions.remote(obs))

    def start_rollout(self, worker_id: int, n_envs: int):
        return self._get(self._server.start_rollout.remote(worker_id, n_envs))

    def compute_actions_stacked(self, worker_id, new_frames, reset_mask):
        return self._get(self._server.compute_actions_stacked.remote(
            worker_id, new_frames, reset_mask))

    def value(self, obs):
        return self._get(self._server.value.remote(obs))

    def greedy_action(self, obs):
        return self._get(self._server.greedy_action.remote(obs))

    def action_logp(self, obs, actions):
        return self._get(self._server.action_logp.remote(obs, actions))

    # -- learning -------------------------------------------------------
    def train_on_batch(self, batch, *, num_sgd_iter: int,
                       sgd_minibatch_size: int, required_keys: tuple,
                       seed: int = 0) -> Dict[str, float]:
        cols = {k: batch[k] for k in required_keys}
        return self._get(self._server.train_on_batch.remote(
            cols, num_sgd_iter=num_sgd_iter,
            sgd_minibatch_size=sgd_minibatch_size, seed=seed))

    def learn_on_minibatch(self, batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        return self._get(self._server.train_on_batch.remote(
            dict(batch), num_sgd_iter=1, sgd_minibatch_size=1 << 62))

    # -- weights --------------------------------------------------------
    def get_weights(self):
        return {_SERVER_WEIGHTS_SENTINEL: True}

    def set_weights(self, weights) -> None:
        if isinstance(weights, dict) and weights.get(_SERVER_WEIGHTS_SENTINEL):
            return  # all workers share the server; nothing to ship
        self._get(self._server.set_weights.remote(weights))

    def get_state(self):
        return self._get(self._server.get_state.remote())

    def set_state(self, state):
        self._get(self._server.set_state.remote(state))


def serve_policy(algo_config: Dict[str, Any], obs_dim: int, num_actions: int,
                 *, obs_shape: Optional[tuple] = None, num_tpus: float = 0,
                 max_concurrency: int = 16, frame_stack_transport: bool = False):
    """Start a PolicyServer actor for ``algo_config`` and return its
    handle, plus the config entries that point rollout workers at it::

        handle, overrides = serve_policy(cfg, obs_dim, n_act,
                                         obs_shape=(84, 84, 4), num_tpus=1)
        cfg.update(overrides)

    ``frame_stack_transport=True`` (channel-stacked uint8 image envs whose
    reset stacks copies of the first frame — the DeepMind Atari contract):
    workers ship only each env's NEWEST frame per tick, the server keeps
    the stacks device-resident, and training resolves observations from
    device snapshots — pixels cross the host->device link once instead of
    5x (4x stack redundancy + training re-ship).
    """
    import ray_tpu

    policy_kwargs: Dict[str, Any] = {}
    if obs_shape is not None and len(obs_shape) == 3:
        policy_kwargs["obs_shape"] = tuple(obs_shape)
    opts: Dict[str, Any] = {"max_concurrency": max_concurrency}
    if num_tpus:
        opts["num_tpus"] = num_tpus
    handle = ray_tpu.remote(PolicyServer).options(**opts).remote(
        obs_dim, num_actions, policy_kwargs, algo_config)
    overrides = {
        "_policy_class": RemotePolicy,
        "_policy_kwargs": {"server": handle},
        "_frame_stack_transport": bool(frame_stack_transport),
    }
    return handle, overrides
