"""Pipelined environment interaction: async action fetch, env-slice software
pipelining, and double-buffered obs staging.

Every train loop in this repo has the same serial hot path per env step::

    np_obs = prepare_obs(obs)            # host: allocate + cast/stack
    out    = player_fn(params, np_obs)   # device: dispatch (async)
    acts   = jax.device_get(out)         # host BLOCKS until inference + D2H done
    envs.step(acts)                      # host: device idle the whole time

The device->host copy and the env step are strictly serialized even though
neither needs the other's resources. This module breaks that false dependency
three ways, all behind config flags that default to the exact serial behavior:

1. **Async action fetch** (``fabric.async_fetch``): the D2H copy is *started*
   at dispatch time via ``jax.Array.copy_to_host_async()`` and *harvested*
   (one ``jax.device_get``, now mostly a wait-free memcpy) just before
   ``envs.step`` — so the copy rides under whatever host work sits between
   dispatch and use (buffer writes, fused-train dispatch).
2. **Env-slice software pipelining** (``env.pipeline_slices``): the E env
   columns are split into S independent vector envs (:class:`EnvSliceGroup`);
   :meth:`InteractionPipeline.interact` dispatches the policy per slice and
   then steps slice k on the host while slice k+1's actions are still in
   flight on the device. Recurrent player state and PRNG keys are kept
   per-slice; SAME_STEP autoreset bookkeeping (``final_info`` masks,
   ``final_obs`` object arrays) is merged back to the full-E layout so loops
   are oblivious to the slicing.
3. **Double-buffered obs staging** (:class:`ObsStager`): ``prepare_obs``
   writes into two preallocated host buffers in alternation instead of
   allocating per step. Two buffers, not one, because the previous step's
   staged obs may still back an in-flight host->device transfer.

``pipeline_slices=1`` with async fetch off reduces to exactly the serial
loop — same op order, same PRNG folds, bit-identical rollouts (the
equivalence tests in ``tests/test_core/test_interact.py`` pin this).

gymnasium's ``SyncVectorEnv`` REUSES its observation/reward buffers across
steps, so :meth:`InteractionPipeline.interact` (which steps envs before the
caller's replay-buffer writes) returns obs copied into pipeline-owned
ping-pong buffers — the obs a loop holds stays valid for one full iteration
regardless of what the vector env does underneath.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import gymnasium as gym
import numpy as np

from sheeprl_tpu.telemetry import trace_context
from sheeprl_tpu.telemetry import tracer as tracer_mod

_MISSING = object()

OVERLAP_GAUGE = "interaction_overlap_fraction"
ASYNC_BYTES_COUNTER = "async_fetch_bytes"
BLOCKING_CALLS_COUNTER = "blocking_fetch_calls"


# --------------------------------------------------------------------- trees
def split_ranges(num_envs: int, slices: int) -> List[Tuple[int, int]]:
    """Partition ``num_envs`` columns into ``slices`` contiguous ranges
    (first ``num_envs % slices`` ranges get one extra column, matching
    ``np.array_split``)."""
    if slices < 1:
        raise ValueError(f"pipeline_slices must be >= 1, got {slices}")
    if slices > num_envs:
        raise ValueError(f"pipeline_slices ({slices}) cannot exceed num_envs ({num_envs})")
    base, extra = divmod(num_envs, slices)
    ranges: List[Tuple[int, int]] = []
    start = 0
    for k in range(slices):
        stop = start + base + (1 if k < extra else 0)
        ranges.append((start, stop))
        start = stop
    return ranges


def tree_slice(tree: Any, start: int, stop: int) -> Any:
    """Slice axis 0 of every array leaf of a (possibly dict) obs tree."""
    if isinstance(tree, dict):
        return {k: tree_slice(v, start, stop) for k, v in tree.items()}
    return tree[start:stop]


def tree_concat(parts: Sequence[Any]) -> Any:
    """Concatenate per-slice obs/output trees back to the full-E layout."""
    first = parts[0]
    if isinstance(first, dict):
        return {k: tree_concat([p[k] for p in parts]) for k in first}
    if isinstance(first, (tuple, list)):
        return type(first)(tree_concat([p[i] for p in parts]) for i in range(len(first)))
    return np.concatenate([np.asarray(p) for p in parts], axis=0)


def _zeros_like_rows(template: np.ndarray, n: int) -> np.ndarray:
    if template.dtype == object:
        return np.full((n,) + template.shape[1:], None, dtype=object)
    return np.zeros((n,) + template.shape[1:], dtype=template.dtype)


def merge_infos(infos: Sequence[Dict[str, Any]], counts: Sequence[int]) -> Dict[str, Any]:
    """Merge per-slice vector-env infos back to the full-E layout.

    gymnasium's SAME_STEP autoreset info protocol is per-env arrays plus
    ``_``-prefixed boolean presence masks, nested dicts (``final_info`` →
    ``episode``), and object arrays (``final_obs``). A slice in which no env
    finished simply lacks the key, so absent entries are filled with zeros
    (False for masks, None for object arrays) — exactly what one big vector
    env would have produced for those columns."""
    keys: List[str] = []
    for inf in infos:
        for k in inf:
            if k not in keys:
                keys.append(k)
    merged: Dict[str, Any] = {}
    for key in keys:
        vals = [inf.get(key, _MISSING) for inf in infos]
        merged[key] = _merge_info_value(vals, counts)
    return merged


def _merge_info_value(vals: Sequence[Any], counts: Sequence[int]) -> Any:
    template = None
    template_count = 0
    for v, n in zip(vals, counts):
        if v is not _MISSING:
            template, template_count = v, n
            break
    if isinstance(template, dict):
        return merge_infos([v if isinstance(v, dict) else {} for v in vals], counts)
    if isinstance(template, np.ndarray) and template.ndim >= 1 and len(template) == template_count:
        parts = [
            _zeros_like_rows(template, n) if v is _MISSING else np.asarray(v)
            for v, n in zip(vals, counts)
        ]
        return np.concatenate(parts, axis=0)
    # Scalar / non-per-env payload: first present value wins.
    return template


# ------------------------------------------------------------ EnvSliceGroup
class EnvSliceGroup(gym.vector.VectorEnv):
    """S independent vector envs presented as one ``num_envs``-wide vector env.

    Built by :func:`sheeprl_tpu.utils.env.make_vector_env` when
    ``env.pipeline_slices > 1``. The combined :meth:`step`/:meth:`reset`
    surface is drop-in for every loop (slices step sequentially, same per-env
    call order as one big ``SyncVectorEnv``, so env RNG streams match); the
    per-slice :meth:`step_slice` is what
    :meth:`InteractionPipeline.interact` pipelines against.

    Seeding matches the monolithic env: ``reset(seed=s)`` seeds slice k with
    ``s + start_k``, and gymnasium's vector reset adds the within-slice index,
    so global env j always sees ``s + j``."""

    def __init__(self, envs: Sequence[gym.vector.VectorEnv]) -> None:
        if not envs:
            raise ValueError("EnvSliceGroup needs at least one sub vector env")
        self.envs: List[gym.vector.VectorEnv] = list(envs)
        counts = [e.num_envs for e in self.envs]
        self.num_envs = int(sum(counts))
        self.slice_ranges = []
        start = 0
        for n in counts:
            self.slice_ranges.append((start, start + n))
            start += n
        first = self.envs[0]
        self.single_observation_space = first.single_observation_space
        self.single_action_space = first.single_action_space
        self.observation_space = gym.vector.utils.batch_space(
            self.single_observation_space, self.num_envs
        )
        self.action_space = gym.vector.utils.batch_space(self.single_action_space, self.num_envs)
        self.metadata = first.metadata
        self.render_mode = getattr(first, "render_mode", None)
        self.spec = getattr(first, "spec", None)

    @property
    def slices(self) -> int:
        return len(self.envs)

    @property
    def slice_counts(self) -> List[int]:
        return [s1 - s0 for s0, s1 in self.slice_ranges]

    def reset(
        self, *, seed: Optional[Any] = None, options: Optional[dict] = None
    ) -> Tuple[Any, Dict[str, Any]]:
        obs_parts: List[Any] = []
        info_parts: List[Dict[str, Any]] = []
        for (s0, s1), env in zip(self.slice_ranges, self.envs):
            if isinstance(seed, int):
                sub_seed: Optional[Any] = seed + s0
            elif isinstance(seed, (list, tuple)):
                sub_seed = list(seed[s0:s1])
            else:
                sub_seed = seed
            obs, info = env.reset(seed=sub_seed, options=options)
            obs_parts.append(obs)
            info_parts.append(info)
        return tree_concat(obs_parts), merge_infos(info_parts, self.slice_counts)

    def step_slice(self, k: int, actions: Any) -> Tuple[Any, Any, Any, Any, Dict[str, Any]]:
        """Step ONLY slice k (actions in slice-local layout)."""
        return self.envs[k].step(actions)

    def step(self, actions: Any) -> Tuple[Any, Any, Any, Any, Dict[str, Any]]:
        results = []
        for k, (s0, s1) in enumerate(self.slice_ranges):
            results.append(self.step_slice(k, tree_slice(actions, s0, s1)))
        return self.merge_step(results)

    def merge_step(
        self, results: Sequence[Tuple[Any, Any, Any, Any, Dict[str, Any]]]
    ) -> Tuple[Any, Any, Any, Any, Dict[str, Any]]:
        counts = self.slice_counts
        obs = tree_concat([r[0] for r in results])
        rewards = np.concatenate([np.asarray(r[1]) for r in results], axis=0)
        terminated = np.concatenate([np.asarray(r[2]) for r in results], axis=0)
        truncated = np.concatenate([np.asarray(r[3]) for r in results], axis=0)
        infos = merge_infos([r[4] for r in results], counts)
        return obs, rewards, terminated, truncated, infos

    def call(self, name: str, *args: Any, **kwargs: Any) -> tuple:
        out: List[Any] = []
        for env in self.envs:
            out.extend(env.call(name, *args, **kwargs))
        return tuple(out)

    def close(self, **kwargs: Any) -> None:
        for env in self.envs:
            env.close(**kwargs)


# ---------------------------------------------------------------- ObsStager
class ObsStager:
    """Double-buffered ``prepare_obs`` staging.

    Wraps a ``prepare(obs, out=None) -> host tree`` callable. The first two
    calls allocate (as today); afterwards the two result trees are reused in
    alternation via the ``out=`` parameter, so steady-state staging performs
    zero allocations. Two buffers because buffer t-1 may still back an
    in-flight host->device transfer when step t stages."""

    __slots__ = ("_prepare", "_buffers", "_idx")

    def __init__(self, prepare: Callable[..., Any]) -> None:
        self._prepare = prepare
        self._buffers: List[Any] = [None, None]
        self._idx = 0

    def __call__(self, obs: Any) -> Any:
        self._idx ^= 1
        out = self._prepare(obs, out=self._buffers[self._idx])
        self._buffers[self._idx] = out
        return out


# -------------------------------------------------------------------- stats
class FetchStats:
    """Per-run interaction accounting (one instance per pipeline)."""

    __slots__ = (
        "steps",
        "async_fetches",
        "blocking_fetches",
        "async_fetch_bytes",
        "fetch_blocked_s",
        "fetch_ride_s",
        "policy_dispatch_s",
        "env_step_s",
    )

    def __init__(self) -> None:
        self.steps = 0
        self.async_fetches = 0
        self.blocking_fetches = 0
        self.async_fetch_bytes = 0
        self.fetch_blocked_s = 0.0
        self.fetch_ride_s = 0.0
        self.policy_dispatch_s = 0.0
        self.env_step_s = 0.0

    @property
    def overlap_fraction(self) -> float:
        """Fraction of total fetch latency hidden under other host work:
        ride / (ride + blocked). 0.0 when fully serial, -> 1.0 when every
        copy finishes before its harvest."""
        total = self.fetch_ride_s + self.fetch_blocked_s
        return self.fetch_ride_s / total if total > 0 else 0.0

    def as_dict(self) -> Dict[str, float]:
        return {
            "steps": self.steps,
            "async_fetches": self.async_fetches,
            "blocking_fetches": self.blocking_fetches,
            "async_fetch_bytes": self.async_fetch_bytes,
            "fetch_blocked_s": self.fetch_blocked_s,
            "fetch_ride_s": self.fetch_ride_s,
            "policy_dispatch_s": self.policy_dispatch_s,
            "env_step_s": self.env_step_s,
            "overlap_fraction": self.overlap_fraction,
        }


# A reader on another thread may take the slot while a decoupled trainer
# is still publishing; swap under the lock.
_stats_lock = threading.Lock()
_LAST_RUN_STATS: Optional[Dict[str, float]] = None  # graftlint: guarded-by(_stats_lock)


def last_run_stats() -> Optional[Dict[str, float]]:
    """The stats dict from the most recent :meth:`InteractionPipeline.publish`
    in this process: a run's interaction time split without parsing logs."""
    with _stats_lock:
        return _LAST_RUN_STATS


# ------------------------------------------------------------- PendingFetch
class PendingFetch:
    """Handle for one device->host action fetch.

    Created at dispatch time by :meth:`InteractionPipeline.fetch`; in async
    mode the D2H copy of every ``jax.Array`` leaf is started immediately
    (``copy_to_host_async``). :meth:`harvest` performs the one blocking
    ``jax.device_get`` and books the time split: submit→harvest is *ride*
    (hidden under host work), the ``device_get`` duration is *blocked*."""

    __slots__ = ("_pipeline", "_tree", "_label", "_async", "_submit_t", "_result", "_done", "_ctx")

    def __init__(self, pipeline: "InteractionPipeline", tree: Any, label: str) -> None:
        self._pipeline = pipeline
        self._tree = tree
        self._label = label
        self._async = pipeline.async_fetch
        self._result: Any = None
        self._done = False
        # Captured at dispatch: the harvest may happen an iteration later
        # (or after other work), but the fetch span belongs causally to the
        # iteration that dispatched it.
        parent = trace_context.current()
        self._ctx = parent.child() if parent is not None else None
        if self._async:
            import jax

            nbytes = 0
            for leaf in jax.tree_util.tree_leaves(tree):
                start = getattr(leaf, "copy_to_host_async", None)
                if start is not None:
                    start()
                    nbytes += int(getattr(leaf, "nbytes", 0))
            stats = pipeline.stats
            stats.async_fetches += 1
            stats.async_fetch_bytes += nbytes
            tracer = tracer_mod.current()
            tracer.count(ASYNC_BYTES_COUNTER, nbytes)
        self._submit_t = time.perf_counter()

    def harvest(self) -> Any:
        """Blocking ``device_get``; idempotent (later calls return the cached
        host tree)."""
        if self._done:
            return self._result
        import jax

        from sheeprl_tpu.core import chaos

        tracer = tracer_mod.current()
        t0 = time.perf_counter()
        watchdog = self._pipeline.watchdog
        with tracer.span(f"fetch/{self._label}", "fetch", ctx=self._ctx) as span:
            if watchdog is not None:
                with watchdog.guard(f"fetch/{self._label}"):
                    # Inside the armed window: a delayed_fetch drill must look
                    # exactly like a hung device fetch to the watchdog.
                    chaos.maybe_delay("fetch.harvest")
                    out = jax.device_get(self._tree)
            else:
                chaos.maybe_delay("fetch.harvest")
                out = jax.device_get(self._tree)
            t1 = time.perf_counter()
            if tracer.enabled:
                nbytes = tracer_mod.tree_bytes(out)
                span.set(bytes=nbytes, **{"async": self._async})
                tracer.count("device_get_calls", 1)
                tracer.count("device_get_bytes", nbytes)
        stats = self._pipeline.stats
        stats.fetch_blocked_s += t1 - t0
        if self._async:
            stats.fetch_ride_s += t0 - self._submit_t
        else:
            stats.blocking_fetches += 1
            tracer.count(BLOCKING_CALLS_COUNTER, 1)
        self._result = out
        self._done = True
        self._tree = None
        return out


class InteractionResult(NamedTuple):
    outputs: Any
    obs: Any
    rewards: np.ndarray
    terminated: np.ndarray
    truncated: np.ndarray
    infos: Dict[str, Any]


# ------------------------------------------------------- InteractionPipeline
class InteractionPipeline:
    """Orchestrates the per-step interaction of one train loop.

    Two usage levels:

    - **fetch-only** (every loop): replace ``telemetry.fetch(tree)`` with
      ``pending = pipeline.fetch(tree)`` at dispatch time and
      ``pending.harvest()`` at first use. With ``async_fetch`` off this is
      op-for-op the old blocking fetch (just moved to the use site, which
      changes no numerics); with it on, the copy rides under the host work
      in between.
    - **full interact()** (dreamer_v3 / sac / ppo): the pipeline owns the
      slice loop — obs slicing + staging, per-slice policy dispatch, async
      fetch, per-slice env stepping, and the merge back to full-E layout —
      plus per-slice recurrent state (:meth:`init_state` / :meth:`map_state`)
      and per-slice PRNG keys (:meth:`set_key`).

    At ``slices == 1`` and async off, :meth:`interact` performs exactly
    prepare → dispatch → device_get → envs.step with the loop's own key
    passed through untouched: bit-identical to the serial loop.
    """

    def __init__(
        self,
        num_envs: int,
        *,
        slices: int = 1,
        async_fetch: bool = False,
        name: str = "interaction",
    ) -> None:
        self.num_envs = int(num_envs)
        self.slices = int(slices)
        self.async_fetch = bool(async_fetch)
        self.name = name
        self._ranges = split_ranges(self.num_envs, self.slices)
        self.stats = FetchStats()
        # Optional DispatchWatchdog (core/resilience.py) armed around every
        # blocking harvest; loops install it right after construction.
        self.watchdog: Optional[Any] = None
        self._states: Optional[List[Any]] = None
        self._keys: Optional[List[Any]] = None
        self._stagers: Dict[int, ObsStager] = {}
        self._obs_bufs: List[Any] = [None, None]
        self._obs_idx = 0

    @classmethod
    def from_config(
        cls, cfg: Any, num_envs: Optional[int] = None, name: str = "interaction"
    ) -> "InteractionPipeline":
        """Build from the composed run config: ``env.pipeline_slices`` and
        ``fabric.async_fetch`` (both optional, defaulting to the serial
        behavior)."""
        n = int(num_envs if num_envs is not None else cfg.env.num_envs)
        slices = int(cfg.env.get("pipeline_slices", 1) or 1)
        async_fetch = bool(cfg.fabric.get("async_fetch", False))
        return cls(n, slices=slices, async_fetch=async_fetch)

    # ------------------------------------------------------------ fetch-only
    def fetch(self, tree: Any, label: str = "player_actions") -> PendingFetch:
        """Submit a device->host fetch NOW (async copy if enabled); call
        ``.harvest()`` on the returned handle where the host values are
        first needed."""
        return PendingFetch(self, tree, label)

    @property
    def overlap_train(self) -> bool:
        """Whether a loop should dispatch its (fused) train step between
        fetch submit and harvest. Pure host-side reordering — train then
        sees replay data through step t-1 instead of t, the documented
        one-step staleness relaxation — so it is only worth doing when the
        fetch is actually async."""
        return self.async_fetch

    # ---------------------------------------------------------- slice state
    @property
    def slice_ranges(self) -> List[Tuple[int, int]]:
        return list(self._ranges)

    def init_state(self, fn: Callable[[int, Tuple[int, int]], Any]) -> None:
        """Initialize per-slice recurrent player state:
        ``fn(n_envs_in_slice, (start, stop)) -> state``."""
        self._states = [fn(s1 - s0, (s0, s1)) for s0, s1 in self._ranges]

    def map_state(self, fn: Callable[[Any, Tuple[int, int]], Any]) -> None:
        """Transform every slice's state (e.g. masked reset on done envs):
        ``fn(state, (start, stop)) -> new state``. The mask the caller closes
        over is in GLOBAL env coordinates; ``(start, stop)`` selects the
        slice's columns."""
        if self._states is None:
            raise RuntimeError("init_state() was never called")
        self._states = [fn(s, rng) for s, rng in zip(self._states, self._ranges)]

    @property
    def states(self) -> Optional[List[Any]]:
        return self._states

    def set_key(self, key: Any) -> None:
        """Hand the rollout PRNG key to the pipeline. At ``slices == 1`` the
        key passes through the policy untouched (exact serial semantics); at
        S > 1 it is split once into S independent per-slice streams."""
        if self.slices == 1:
            self._keys = [key]
        else:
            import jax

            self._keys = list(jax.random.split(key, self.slices))

    @property
    def key(self) -> Any:
        """The (first) rollout key — for checkpointing at ``slices == 1``."""
        return self._keys[0] if self._keys else None

    # ------------------------------------------------------------- interact
    def _stager(self, k: int, prepare: Callable[..., Any]) -> ObsStager:
        st = self._stagers.get(k)
        if st is None:
            st = ObsStager(prepare)
            self._stagers[k] = st
        return st

    def stash_obs(self, obs: Any) -> Any:
        """Copy merged next-obs into pipeline-owned ping-pong buffers.
        gymnasium vector envs reuse their observation buffer across steps;
        the copy makes the obs a loop holds valid for a full iteration.
        :meth:`interact` stashes automatically; loops with a non-pipelined
        branch (off-policy prefill steps env directly with random actions)
        call this on that branch's obs so the two paths stay aliasing-safe
        with each other."""

        def _copy_into(buf: Any, src: Any) -> Any:
            if isinstance(src, dict):
                if not isinstance(buf, dict):
                    return {k: _copy_into(None, v) for k, v in src.items()}
                return {k: _copy_into(buf.get(k), v) for k, v in src.items()}
            src_arr = np.asarray(src)
            if (
                isinstance(buf, np.ndarray)
                and buf.shape == src_arr.shape
                and buf.dtype == src_arr.dtype
            ):
                np.copyto(buf, src_arr)
                return buf
            return src_arr.copy()

        self._obs_idx ^= 1
        out = _copy_into(self._obs_bufs[self._obs_idx], obs)
        self._obs_bufs[self._obs_idx] = out
        return out

    def interact(
        self,
        envs: gym.vector.VectorEnv,
        obs: Any,
        policy: Callable[[Any, Any, Any], Tuple[Any, Any, Any]],
        *,
        prepare: Optional[Callable[..., Any]] = None,
        to_env_actions: Optional[Callable[[Any, int], Any]] = None,
        before_harvest: Optional[Callable[[], None]] = None,
        label: str = "player_actions",
    ) -> InteractionResult:
        """One full pipelined env step.

        ``policy(np_obs, state, key) -> (fetch_tree, new_state, new_key)`` is
        called once per slice (state/key are ``None`` when unused);
        ``prepare(obs_slice, out=None)`` stages the raw obs slice (double
        buffered per slice); ``to_env_actions(host_outputs, n_envs)`` maps
        the harvested host tree to the env action array.

        ``before_harvest`` runs after every slice's policy has been
        dispatched and its fetch submitted, but before the first harvest —
        the slot where off-policy loops dispatch their fused train step so
        train compute overlaps the action copy and the host env step.

        Dispatch order: every slice's policy is dispatched and its fetch
        submitted first (device queue is deep, dispatch is cheap), then
        slices are harvested and stepped in order — slice k steps on the
        host while slice k+1's copy is still in flight.
        """
        S = self.slices
        tracer = tracer_mod.current()
        use_slices = S > 1
        if use_slices and not (isinstance(envs, EnvSliceGroup) and envs.slices == S):
            raise ValueError(
                f"pipeline_slices={S} requires an EnvSliceGroup with {S} slices "
                "(build envs through make_vector_env)"
            )
        pendings: List[PendingFetch] = []
        t_dispatch = time.perf_counter()
        for k, (s0, s1) in enumerate(self._ranges):
            obs_k = obs if not use_slices else tree_slice(obs, s0, s1)
            np_obs = self._stager(k, prepare)(obs_k) if prepare is not None else obs_k
            state_k = self._states[k] if self._states is not None else None
            key_k = self._keys[k] if self._keys is not None else None
            with tracer.span(f"{self.name}/dispatch/slice{k}", "interaction"):
                fetch_tree, new_state, new_key = policy(np_obs, state_k, key_k)
            if self._states is not None:
                self._states[k] = new_state
            if self._keys is not None:
                self._keys[k] = new_key
            pendings.append(self.fetch(fetch_tree, label=label))
        self.stats.policy_dispatch_s += time.perf_counter() - t_dispatch
        if before_harvest is not None:
            before_harvest()
        outputs_parts: List[Any] = []
        step_parts: List[Tuple[Any, Any, Any, Any, Dict[str, Any]]] = []
        for k, (s0, s1) in enumerate(self._ranges):
            host = pendings[k].harvest()
            outputs_parts.append(host)
            acts = to_env_actions(host, s1 - s0) if to_env_actions is not None else host
            t0 = time.perf_counter()
            with tracer.span(f"{self.name}/env_step/slice{k}", "interaction"):
                if use_slices:
                    step_parts.append(envs.step_slice(k, acts))
                else:
                    step_parts.append(envs.step(acts))
            self.stats.env_step_s += time.perf_counter() - t0
        self.stats.steps += 1
        if use_slices:
            outputs = tree_concat(outputs_parts)
            next_obs, rewards, terminated, truncated, infos = envs.merge_step(step_parts)
        else:
            outputs = outputs_parts[0]
            next_obs, rewards, terminated, truncated, infos = step_parts[0]
        next_obs = self.stash_obs(next_obs)
        if self.stats.steps % 128 == 0:
            tracer.set_gauge(OVERLAP_GAUGE, self.stats.overlap_fraction)
        return InteractionResult(outputs, next_obs, rewards, terminated, truncated, infos)

    # -------------------------------------------------------------- publish
    def snapshot(self) -> Dict[str, float]:
        return self.stats.as_dict()

    def publish(self) -> Dict[str, float]:
        """End-of-run: publish the stats dict to the module-level
        :func:`last_run_stats` slot and the
        overlap-fraction gauge to the current tracer."""
        global _LAST_RUN_STATS
        stats = self.snapshot()
        with _stats_lock:
            _LAST_RUN_STATS = stats
        tracer_mod.current().set_gauge(OVERLAP_GAUGE, stats["overlap_fraction"])
        return stats
