"""Anakin-lane env tests: pure-JAX dynamics vs Gymnasium step-for-step,
the adapter registry, the reverse JaxToGymnasium wrapper, and the in-scan
SAME_STEP autoreset semantics the fused loop relies on."""

import gymnasium as gym
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sheeprl_tpu.envs.jax import (
    CartPole,
    Gridworld,
    GymnaxAdapter,
    JaxToGymnasium,
    Pendulum,
    action_to_env,
    canonical_action_space,
    make_jax_env,
    register_jax_env,
    registered_jax_envs,
)
from sheeprl_tpu.envs.jax.adapter import _normalize


class TestCartPoleEquivalence:
    def test_step_matches_gymnasium_transition(self):
        """Walk both transition functions in lockstep: each step copies the
        jax state into gymnasium's ``env.unwrapped.state`` so per-step
        outputs (obs, reward, terminated) are compared without drift."""
        jenv = CartPole()
        genv = gym.make("CartPole-v1")
        genv.reset(seed=0)
        rng = np.random.default_rng(0)
        state, obs = jax.jit(jenv.reset)(jax.random.PRNGKey(7))
        step = jax.jit(jenv.step)
        for t in range(60):
            genv.unwrapped.state = np.asarray(state["s"], np.float64)
            action = int(rng.integers(0, 2))
            g_obs, g_rew, g_term, g_trunc, _ = genv.step(action)
            state, obs, rew, done, info = step(state, jnp.asarray(action), jax.random.PRNGKey(t))
            np.testing.assert_allclose(np.asarray(obs), g_obs, rtol=1e-5, atol=1e-5)
            assert float(rew) == pytest.approx(g_rew)
            assert bool(info["terminated"]) == g_term
            if g_term:
                break
            # Keep episode-clock parity: gymnasium's TimeLimit lives in the
            # wrapper while the jax env counts in-state.
            assert bool(info["truncated"]) == g_trunc
        genv.close()

    def test_full_episode_from_shared_start_terminates_on_same_step(self):
        jenv = CartPole()
        genv = gym.make("CartPole-v1")
        genv.reset(seed=0)
        state, _ = jenv.reset(jax.random.PRNGKey(3))
        genv.unwrapped.state = np.asarray(state["s"], np.float64)
        step = jax.jit(jenv.step)
        rng = np.random.default_rng(3)
        for t in range(600):
            action = int(rng.integers(0, 2))
            _, _, g_term, g_trunc, _ = genv.step(action)
            state, _, _, done, info = step(state, jnp.asarray(action), jax.random.PRNGKey(t))
            assert bool(done) == (g_term or g_trunc), f"episode end diverged at step {t}"
            if g_term or g_trunc:
                break
        else:
            pytest.fail("episode never ended")
        genv.close()

    def test_truncates_at_500_like_timelimit(self):
        jenv = CartPole()
        state = {"s": jnp.zeros((4,), jnp.float32), "t": jnp.asarray(499, jnp.int32)}
        _, _, _, done, info = jenv.step(state, jnp.asarray(0), jax.random.PRNGKey(0))
        assert bool(done) and bool(info["truncated"]) and not bool(info["terminated"])


class TestPendulumEquivalence:
    def test_step_matches_gymnasium_transition(self):
        jenv = Pendulum()
        genv = gym.make("Pendulum-v1")
        genv.reset(seed=0)
        rng = np.random.default_rng(1)
        state, obs = jenv.reset(jax.random.PRNGKey(11))
        step = jax.jit(jenv.step)
        for t in range(50):
            genv.unwrapped.state = np.asarray(state["s"], np.float64)
            action = rng.uniform(-2.0, 2.0, size=(1,)).astype(np.float32)
            g_obs, g_rew, _, _, _ = genv.step(action)
            state, obs, rew, _, _ = step(state, jnp.asarray(action), jax.random.PRNGKey(t))
            np.testing.assert_allclose(np.asarray(obs), g_obs, rtol=1e-4, atol=1e-4)
            assert float(rew) == pytest.approx(float(g_rew), rel=1e-4, abs=1e-4)
        genv.close()

    def test_reset_distribution_bounds(self):
        jenv = Pendulum()
        state, obs = jenv.reset(jax.random.PRNGKey(0))
        th, thdot = float(state["s"][0]), float(state["s"][1])
        assert -np.pi <= th <= np.pi and -1.0 <= thdot <= 1.0
        np.testing.assert_allclose(np.asarray(obs), [np.cos(th), np.sin(th), thdot], rtol=1e-6)

    def test_truncates_at_200(self):
        jenv = Pendulum()
        state = {"s": jnp.zeros((2,), jnp.float32), "t": jnp.asarray(199, jnp.int32)}
        _, _, _, done, info = jenv.step(state, jnp.zeros((1,)), jax.random.PRNGKey(0))
        assert bool(done) and bool(info["truncated"])


class TestGridworld:
    def test_obs_shape_dtype_and_reset_invariants(self):
        env = Gridworld(grid_size=8, screen_size=64)
        assert env.observation_space.shape == (64, 64, 3)
        for seed in range(8):
            state, obs = env.reset(jax.random.PRNGKey(seed))
            assert obs.shape == (64, 64, 3) and obs.dtype == jnp.uint8
            assert not bool(jnp.all(state["agent"] == state["goal"])), "spawned on the goal"

    def test_reaching_goal_terminates_with_reward(self):
        env = Gridworld(grid_size=2, screen_size=4)
        state = {
            "agent": jnp.asarray([0, 0], jnp.int32),
            "goal": jnp.asarray([0, 1], jnp.int32),
            "t": jnp.zeros((), jnp.int32),
        }
        new_state, _, reward, done, info = env.step(state, jnp.asarray(3), jax.random.PRNGKey(0))
        assert bool(done) and bool(info["terminated"])
        assert float(reward) == pytest.approx(1.0)

    def test_step_penalty_and_wall_clipping(self):
        env = Gridworld(grid_size=2, screen_size=4, step_penalty=0.01)
        state = {
            "agent": jnp.asarray([0, 0], jnp.int32),
            "goal": jnp.asarray([1, 1], jnp.int32),
            "t": jnp.zeros((), jnp.int32),
        }
        # Moving up from row 0 clips at the wall: position unchanged.
        new_state, _, reward, done, _ = env.step(state, jnp.asarray(0), jax.random.PRNGKey(0))
        assert not bool(done)
        assert float(reward) == pytest.approx(-0.01)
        np.testing.assert_array_equal(np.asarray(new_state["agent"]), [0, 0])

    def test_screen_size_must_divide(self):
        with pytest.raises(ValueError, match="multiple"):
            Gridworld(grid_size=7, screen_size=64)


class TestAdapterRegistry:
    def test_id_normalization(self):
        assert _normalize("CartPole-v1") == "cartpole"
        assert _normalize("jax_pendulum") == "pendulum"
        assert _normalize("Jax_GridWorld") == "gridworld"

    def test_first_party_envs_registered(self):
        known = registered_jax_envs()
        for name in ("cartpole", "pendulum", "gridworld"):
            assert name in known
        assert isinstance(make_jax_env("jax_cartpole"), CartPole)
        assert isinstance(make_jax_env("Pendulum-v1"), Pendulum)

    def test_unknown_id_raises_with_known_list(self):
        with pytest.raises(ValueError, match="cartpole"):
            make_jax_env("nope_not_an_env")

    def test_register_custom_env(self):
        sentinel = CartPole()
        register_jax_env("my_env-v3", lambda: sentinel)
        try:
            assert make_jax_env("jax_my_env") is sentinel
        finally:
            from sheeprl_tpu.envs.jax import adapter

            adapter._REGISTRY.pop("my_env", None)

    def test_gymnax_adapter_protocol_reshuffle(self):
        class FakeGymnaxEnv:
            """Minimal gymnax-style env: reset(key, params) -> (obs, state),
            step(key, state, action, params) -> (obs, state, reward, done, info)."""

            default_params = {"limit": 3}

            def observation_space(self, params):
                class Space:
                    low, high, shape, dtype = -1.0, 1.0, (2,), np.float32

                return Space()

            def action_space(self, params):
                class Space:
                    n = 2

                return Space()

            def reset(self, key, params):
                obs = jnp.zeros((2,), jnp.float32)
                return obs, {"t": jnp.zeros((), jnp.int32)}

            def step(self, key, state, action, params):
                t = state["t"] + 1
                done = t >= params["limit"]
                obs = jnp.full((2,), t, jnp.float32)
                return obs, {"t": t}, jnp.asarray(0.5, jnp.float32), done, {}

        env = GymnaxAdapter(FakeGymnaxEnv())
        assert isinstance(env.observation_space, gym.spaces.Box)
        assert isinstance(env.action_space, gym.spaces.Discrete)
        key = jax.random.PRNGKey(0)
        state, obs = env.reset(key)
        for _ in range(3):
            state, obs, reward, done, info = env.step(state, jnp.asarray(1), key)
        assert bool(done)
        # gymnax collapses TimeLimit into done: maps to terminated here.
        assert bool(info["terminated"]) and not bool(info["truncated"])
        assert float(reward) == pytest.approx(0.5)


class TestCanonicalActions:
    def test_box_space_rescaled_to_unit_interval(self):
        env = Pendulum()
        canon = canonical_action_space(env)
        assert isinstance(canon, gym.spaces.Box)
        np.testing.assert_allclose(canon.low, -1.0)
        np.testing.assert_allclose(canon.high, 1.0)
        to_env = action_to_env(env)
        np.testing.assert_allclose(np.asarray(to_env(jnp.asarray([1.0]))), [2.0], rtol=1e-6)
        np.testing.assert_allclose(np.asarray(to_env(jnp.asarray([-1.0]))), [-2.0], rtol=1e-6)
        np.testing.assert_allclose(np.asarray(to_env(jnp.asarray([0.0]))), [0.0], atol=1e-6)
        # Out-of-range canonical actions clip before rescaling.
        np.testing.assert_allclose(np.asarray(to_env(jnp.asarray([5.0]))), [2.0], rtol=1e-6)

    def test_discrete_space_is_identity(self):
        env = CartPole()
        assert canonical_action_space(env) is env.action_space
        a = jnp.asarray(1)
        assert action_to_env(env)(a) is a


class TestJaxToGymnasium:
    def test_gymnasium_contract_and_seed_determinism(self):
        env1 = JaxToGymnasium(id="jax_cartpole", seed=5)
        env2 = JaxToGymnasium(id="jax_cartpole", seed=5)
        obs1, _ = env1.reset()
        obs2, _ = env2.reset()
        np.testing.assert_array_equal(obs1, obs2)
        assert obs1.shape == env1.observation_space.shape
        obs1, r1, t1, tr1, _ = env1.step(1)
        obs2, r2, t2, tr2, _ = env2.step(1)
        np.testing.assert_array_equal(obs1, obs2)
        assert (r1, t1, tr1) == (r2, t2, tr2)
        assert isinstance(r1, float) and isinstance(t1, bool)
        env1.close()
        env2.close()

    def test_reseed_on_reset(self):
        env = JaxToGymnasium(id="jax_pendulum")
        a, _ = env.reset(seed=9)
        b, _ = env.reset(seed=9)
        np.testing.assert_array_equal(a, b)
        env.close()

    def test_step_before_reset_raises(self):
        env = JaxToGymnasium(id="jax_cartpole")
        with pytest.raises(RuntimeError, match="reset"):
            env.step(0)

    def test_pixel_env_renders_last_frame(self):
        env = JaxToGymnasium(id="jax_gridworld")
        obs, _ = env.reset(seed=0)
        frame = env.render()
        np.testing.assert_array_equal(frame, obs)
        env.close()

    def test_wraps_existing_instance_and_requires_something(self):
        env = JaxToGymnasium(env=Pendulum())
        assert isinstance(env.jax_env, Pendulum)
        with pytest.raises(ValueError, match="id"):
            JaxToGymnasium()


class TestInScanAutoreset:
    """The fused loop's SAME_STEP autoreset: on a done step the trajectory
    stores the terminal transition (pre-reset obs, terminal reward,
    done=True) while the scan carry moves to a freshly reset episode."""

    def _scan(self, env, n_envs, steps, actions, seed=0, init=None):
        from sheeprl_tpu.core.fused_loop import _where_done

        reset_v = jax.vmap(env.reset)
        step_v = jax.vmap(env.step)
        if init is None:
            init_state, init_obs = reset_v(jax.random.split(jax.random.PRNGKey(seed), n_envs))
        else:
            init_state, init_obs = init

        def body(carry, inp):
            env_state, obs = carry
            action, key = inp
            k_step, k_reset = jax.random.split(key)
            env_state, new_obs, reward, done, info = step_v(
                env_state, action, jax.random.split(k_step, n_envs)
            )
            reset_state, reset_obs = reset_v(jax.random.split(k_reset, n_envs))
            carried_state = jax.tree_util.tree_map(
                lambda a, b: _where_done(done, a, b), reset_state, env_state
            )
            carried_obs = _where_done(done, reset_obs, new_obs)
            traj = {"obs": obs, "reward": reward, "done": done, "post_t": carried_state["t"]}
            return (carried_state, carried_obs), traj

        keys = jax.random.split(jax.random.PRNGKey(seed + 1), steps)
        (final_state, final_obs), traj = jax.lax.scan(body, (init_state, init_obs), (actions, keys))
        return init_obs, traj, final_state

    @staticmethod
    def _known_start(env, steps):
        # Single env with a KNOWN start: agent (0,0), goal (1,1), policy
        # right/down — the first episode deterministically terminates at the
        # second step, so the scan crosses an episode boundary whatever the
        # PRNG draws.
        init_state = {
            "agent": jnp.asarray([[0, 0]], jnp.int32),
            "goal": jnp.asarray([[1, 1]], jnp.int32),
            "t": jnp.zeros((1,), jnp.int32),
        }
        init_obs = jax.vmap(env._render)(init_state["agent"], init_state["goal"])
        actions = jnp.asarray([[3], [1]] * (steps // 2), jnp.int32).reshape(steps, 1)
        return (init_state, init_obs), actions

    def test_done_row_keeps_terminal_transition_and_carry_resets(self):
        env = Gridworld(grid_size=2, screen_size=4)
        steps = 8
        init, actions = self._known_start(env, steps)
        init_obs, traj, final_state = self._scan(env, 1, steps, actions, init=init)
        done = np.asarray(traj["done"]).reshape(steps)
        reward = np.asarray(traj["reward"]).reshape(steps)
        post_t = np.asarray(traj["post_t"]).reshape(steps)
        assert done.any(), "no episode ended in the scan window"
        for t in range(steps):
            if done[t]:
                # SAME_STEP: the row holds the terminal reward...
                assert reward[t] == pytest.approx(1.0)
                # ...and the carry left the step freshly reset (t == 0).
                assert post_t[t] == 0
            else:
                assert post_t[t] == t + 1 - (np.flatnonzero(done[:t])[-1] + 1 if done[:t].any() else 0)

    def test_stored_obs_is_pre_reset(self):
        env = Gridworld(grid_size=2, screen_size=4)
        steps = 6
        init, actions = self._known_start(env, steps)
        init_obs, traj, _ = self._scan(env, 1, steps, actions, init=init)
        done = np.asarray(traj["done"]).reshape(steps)
        obs = np.asarray(traj["obs"])
        t_done = int(np.flatnonzero(done)[0])
        assert t_done == 1
        # Row t stores the obs the action was computed FROM, so the row
        # after a done step must come from the reset episode, not continue
        # the old one: its stored obs differs from what the old episode's
        # next render would have been only if positions moved — weaker but
        # checkable: the post-done row's obs equals the carry the reset
        # produced, i.e. a valid fresh-episode frame with agent != goal.
        frame = obs[t_done + 1, 0]
        red = (frame == np.asarray([220, 40, 40], np.uint8)).all(-1).any()
        green = (frame == np.asarray([40, 220, 40], np.uint8)).all(-1).any()
        assert red and green, "post-done row is not a fresh episode frame"

    def test_matches_host_lane_same_step_semantics(self):
        """The host lane (JaxToGymnasium stepped manually with a reset-on-done
        driver) and the in-scan autoreset agree on WHERE rewards and dones
        land for the same deterministic dynamics."""
        env = Gridworld(grid_size=2, screen_size=4)
        steps = 8
        actions = [3, 1] * (steps // 2)
        # Host side: fresh wrapper, manual SAME_STEP autoreset.
        host = JaxToGymnasium(env=Gridworld(grid_size=2, screen_size=4), seed=0)
        host.reset(seed=0)
        host_rewards, host_dones = [], []
        for a in actions:
            _, r, term, trunc, _ = host.step(a)
            host_rewards.append(r)
            host_dones.append(term or trunc)
            if term or trunc:
                host.reset()
        host.close()
        # Scan side: same action sequence. (Different reset keys give
        # different start cells, so compare the INVARIANT: every done step
        # carries the terminal +1 reward and non-done steps the penalty.)
        acts = jnp.asarray(actions, jnp.int32).reshape(steps, 1)
        _, traj, _ = self._scan(env, 1, steps, acts, seed=0)
        scan_done = np.asarray(traj["done"]).reshape(steps)
        scan_rew = np.asarray(traj["reward"]).reshape(steps)
        for rewards, dones in ((host_rewards, host_dones), (scan_rew, scan_done)):
            for r, d in zip(rewards, dones):
                if d:
                    assert float(r) == pytest.approx(1.0)
                else:
                    assert float(r) == pytest.approx(-0.01)
