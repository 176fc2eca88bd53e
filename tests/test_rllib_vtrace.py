"""RLlib's V-trace learners: IMPALA, APPO (asynchronous sampling under a
clipped surrogate) and the V-trace targets themselves.  Beside
``test_rllib.py`` (whose learning checks these were; a file of their own so
that neither holds a tier-1 worker for more than 300 test-seconds: ROADMAP
D2)."""

import numpy as np

def test_impala_cartpole_learns():
    from ray_tpu.rllib import ImpalaConfig

    algo = (
        ImpalaConfig()
        .environment("CartPole-v1")
        .rollouts(rollout_fragment_length=200)
        .training(train_batch_size=800, lr=2e-3)
        .debugging(seed=0)
        .build()
    )
    best = 0.0
    for _ in range(40):
        r = algo.train()
        best = max(best, r["episode_reward_mean"])
        if best >= 120:
            break
    algo.cleanup()
    assert best >= 120, f"IMPALA failed to improve on CartPole: best={best}"


def test_vtrace_reduces_to_gae_targets_on_policy():
    """With identical behavior/current logp, rho = c = 1 and vs equals the
    discounted return recursion."""
    from ray_tpu.rllib import compute_vtrace

    rng = np.random.default_rng(0)
    T = 6
    logp = rng.normal(size=T).astype(np.float32)
    values = rng.normal(size=T).astype(np.float32)
    rewards = rng.normal(size=T).astype(np.float32)
    gamma = 0.9
    vs, pg_adv, rho = compute_vtrace(
        logp, logp, values, 0.5, rewards, gamma
    )
    assert np.allclose(rho, 1.0)
    # on-policy vs recursion == n-step TD(lambda=1) targets
    expect = np.zeros(T, np.float32)
    boot = 0.5
    acc = boot
    for t in range(T - 1, -1, -1):
        acc = rewards[t] + gamma * acc
        expect[t] = acc
    np.testing.assert_allclose(vs, expect, rtol=1e-5)


def test_appo_async_cartpole_learns(ray_start_regular):
    """APPO: async rollout/learner overlap (workers always have a
    sample in flight; the learner trains on whatever lands first) with
    the clipped surrogate over V-trace-corrected advantages
    (reference rllib/algorithms/appo/appo.py)."""
    from ray_tpu.rllib import APPOConfig

    algo = (
        APPOConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=2, rollout_fragment_length=200)
        .training(train_batch_size=400, lr=3e-3, num_sgd_iter=2,
                  minibatch_size=200, batches_per_step=2)
        .debugging(seed=0)
        .build()
    )
    best = 0.0
    for _ in range(120):
        r = algo.train()
        best = max(best, r["episode_reward_mean"])
        if best >= 120:
            break
    algo.cleanup()
    assert best >= 120, f"APPO failed to improve on CartPole: best={best}"


def test_appo_overlaps_sampling_with_learning(ray_start_regular):
    """The async contract itself: while the learner is inside
    training_step, every rollout worker has a sample() already in
    flight (no sampling barrier)."""
    from ray_tpu.rllib import APPOConfig

    algo = (
        APPOConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=2, rollout_fragment_length=50)
        .training(train_batch_size=100)
        .debugging(seed=0)
        .build()
    )
    algo.train()
    # after a step returns, the workers are re-armed: one in-flight
    # sample per worker is already running
    assert len(algo._inflight) == len(algo.workers.remote_workers)
    algo.cleanup()
    assert not algo._inflight
