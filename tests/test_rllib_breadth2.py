"""RLlib breadth, round 2: DDPG, APPO, MARWIL, Rainbow-lite DQN.

Parity targets (ray): rllib/algorithms/{ddpg,appo,marwil}/ and the
DQN dueling / prioritized_replay config keys (the Rainbow components
the reference exposes on its DQN).
"""

import numpy as np
import pytest

from ray_tpu.rllib import (
    APPOConfig,
    DDPGConfig,
    DQNConfig,
    MARWIL,
    MARWILConfig,
    OfflineDataset,
    SACConfig,
)
from ray_tpu.rllib.env import Pendulum

pytestmark = pytest.mark.long_file(111)


def test_ddpg_runs_pendulum_single_critic():
    algo = (DDPGConfig()
            .environment("Pendulum-v1")
            .training(num_envs=4, steps_per_iteration=128,
                      learning_starts=128, train_batch_size=64)
            .debugging(seed=0)
            .build())
    assert "q2" not in algo.params  # single critic — DDPG, not TD3
    m = algo.train()
    m = algo.train()
    assert np.isfinite(m["critic_loss_mean"])
    a = algo.compute_single_action(np.zeros(3, np.float32), explore=True)
    assert a.shape == (1,)


def test_ddpg_learns_pendulum(learning_table):
    algo = (DDPGConfig()
            .environment("Pendulum-v1")
            .training(num_envs=4, steps_per_iteration=256,
                      learning_starts=500, train_batch_size=128)
            .debugging(seed=0)
            .build())
    rets = []
    for _ in range(25):
        rets.append(algo.train()["episode_return_mean"])
    achieved = float(np.nanmean(rets[-5:]))
    # random ≈ -1250; gate well above it (observed -470..-620).
    learning_table("DDPG", "Pendulum-v1", achieved, -800)
    assert achieved > -800, rets


def test_appo_learns_cartpole(learning_table):
    algo = (APPOConfig()
            .environment("CartPole-v1")
            .training(num_env_runners=2, num_envs=8, rollout_length=64,
                      updates_per_iteration=4, lr=5e-3)
            .debugging(seed=0)
            .build())
    try:
        first = algo.train()
        assert "clip_fraction" in first  # the PPO surrogate ran
        rets = []
        for _ in range(20):
            last = algo.train()
            rets.append(last["episode_return_mean"])
        assert np.isfinite(last["total_loss"])
        achieved = float(np.nanmean(rets[-5:]))
        learning_table("APPO", "CartPole-v1", achieved, 80)
        assert achieved > 80, rets
    finally:
        algo.stop()


def test_rainbow_lite_dqn_learns_cartpole(learning_table):
    """double + dueling + prioritized replay together."""
    algo = (DQNConfig()
            .environment("CartPole-v1")
            .training(num_envs=8, steps_per_iteration=512,
                      learning_starts=500, double_q=True, dueling=True,
                      prioritized_replay=True, lr=1e-3)
            .debugging(seed=0)
            .build())
    assert "torso" in algo.params  # dueling head in use
    rets = []
    for _ in range(12):
        last = algo.train()
        rets.append(last["episode_return_mean"])
    assert np.isfinite(last["loss_mean"])
    achieved = float(np.nanmean(rets[-5:]))
    learning_table("RainbowDQN", "CartPole-v1", achieved, 120)
    assert achieved > 120, rets
    assert algo.compute_single_action(
        np.zeros(4, np.float32)) in range(2)


@pytest.fixture(scope="module")
def pendulum_dataset():
    sac = (SACConfig()
           .environment("Pendulum-v1")
           .training(steps_per_iteration=256, train_batch_size=128,
                     learning_starts=500)
           .debugging(seed=0).build())
    for _ in range(15):
        sac.train()

    def behavior(obs, rng):
        a = sac.compute_single_action(obs)
        return np.clip(a + rng.normal(0, 0.35, a.shape), -2.0, 2.0
                       ).astype(np.float32)

    return OfflineDataset.collect(Pendulum(), behavior,
                                  num_steps=3000, seed=3)


def _rollout_return(env, act_fn, seed=11, episodes=3):
    import jax
    import jax.numpy as jnp

    total = 0.0
    key = jax.random.key(seed)
    for _ in range(episodes):
        key, k = jax.random.split(key)
        state, obs = env.reset(k)
        done = False
        while not done:
            a = act_fn(np.asarray(obs))
            state, obs, r, d = env.step(state, jnp.asarray(a))
            total += float(r)
            done = bool(d)
    return total / episodes


def test_marwil_learns_from_offline_data(pendulum_dataset,
                                         learning_table):
    cfg = MARWILConfig().environment("Pendulum-v1").training(
        updates_per_iteration=64, train_batch_size=256, beta=1.0)
    cfg.dataset = pendulum_dataset
    algo = cfg.debugging(seed=0).build()
    for _ in range(12):
        last = algo.train()
    assert np.isfinite(last["total_loss"])
    assert np.isfinite(last["vf_loss"])
    a = algo.compute_single_action(np.zeros(3, np.float32))
    assert a.shape == (1,) and np.all(np.abs(a) <= 2.0)
    # Behavioral check (vf/clone losses chase bootstrapped, re-weighted
    # targets and are not monotone): the advantage-weighted clone must
    # land near the behavior policy's level, far above random
    # (random ≈ -1450; observed ≈ -580 with GAE advantages + the
    # normalized value head).
    env = Pendulum()
    rng = np.random.default_rng(5)
    rand_ret = _rollout_return(
        env, lambda o: rng.uniform(-2.0, 2.0, (1,)).astype(np.float32))
    marwil_ret = _rollout_return(env, algo.compute_single_action)
    learning_table("MARWIL", "Pendulum-v1", marwil_ret,
                   rand_ret + 500.0)
    assert marwil_ret > rand_ret + 500.0, (marwil_ret, rand_ret)
    # beta=0 degenerates to plain BC (uniform weights) and still runs.
    cfg0 = MARWILConfig().environment("Pendulum-v1").training(beta=0.0)
    cfg0.dataset = pendulum_dataset
    bc_like = cfg0.debugging(seed=0).build()
    assert np.isfinite(bc_like.train()["weighted_clone_loss"])


def test_marwil_requires_dataset():
    with pytest.raises(ValueError):
        MARWILConfig().environment("Pendulum-v1").build()


def test_marwil_checkpoint_roundtrip(pendulum_dataset):
    import jax

    cfg = MARWILConfig().environment("Pendulum-v1")
    cfg.dataset = pendulum_dataset
    algo = cfg.debugging(seed=0).build()
    algo.train()
    state = algo.get_state()
    cfg2 = MARWILConfig().environment("Pendulum-v1")
    cfg2.dataset = pendulum_dataset
    algo2 = cfg2.debugging(seed=0).build()
    algo2.set_state(state)
    for x, y in zip(jax.tree.leaves(algo.params),
                    jax.tree.leaves(algo2.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y))


def test_c51_distributional_dqn_learns_cartpole(learning_table):
    """num_atoms > 1 = C51 (parity: rllib DQN num_atoms/v_min/v_max):
    categorical return distribution + projected-Bellman cross-entropy."""
    algo = (DQNConfig()
            .environment("CartPole-v1")
            .training(num_envs=8, steps_per_iteration=512,
                      learning_starts=500, num_atoms=51, v_min=0.0,
                      v_max=200.0, prioritized_replay=True, lr=1e-3)
            .debugging(seed=0)
            .build())
    # Distributional head: act_dim * atoms outputs, expected-Q greedy.
    import jax.numpy as jnp

    logits = algo._dist_fn(algo.params, jnp.zeros((3, 4)))
    assert logits.shape == (3, 2, 51)
    rets = []
    for _ in range(12):
        last = algo.train()
        rets.append(last["episode_return_mean"])
    assert np.isfinite(last["loss_mean"])
    achieved = float(np.nanmean(rets[-5:]))
    learning_table("C51-DQN", "CartPole-v1", achieved, 100)
    assert achieved > 100, rets
    assert algo.compute_single_action(
        np.zeros(4, np.float32)) in range(2)


def test_c51_rejects_dueling():
    with pytest.raises(ValueError, match="dueling"):
        (DQNConfig().environment("CartPole-v1")
         .training(num_atoms=51, dueling=True).build())
