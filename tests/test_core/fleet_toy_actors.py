"""Toy actor loops for FleetSupervisor unit tests.

Importable by spawn children (the supervisor forwards the parent's sys.path,
which includes this directory), and never touching a JAX backend so each
replica process starts in well under a second. Every shipped row is tagged
with the replica's identity triple (replica, restart, seed) so the
learner-side assertions can reconstruct exactly which process generation
produced it, and with the JAX platform selection the replica entry left in
force before the actor ran.
"""

import os
import sys
import time


def _tagged(ctx, i):
    # jax is imported (not initialised) in every replica: unpickling the
    # spawn arguments imports sheeprl_tpu.core.
    return {
        "replica": ctx.replica,
        "restart": ctx.restart,
        "seed": ctx.seed,
        "i": i,
        "jax_platforms": sys.modules["jax"].config.jax_platforms,
    }


def steady(ctx):
    """Ship cfg.toy_total rows, then return (a clean `complete` bye)."""
    for i in range(int(ctx.cfg.get("toy_total", 5))):
        if ctx.should_stop():
            return
        ctx.ship(_tagged(ctx, i), env_steps=1)
        time.sleep(0.01)


def crash_once(ctx):
    """Die hard (no bye, simulating SIGKILL) mid-stream on generation 0;
    behave like `steady` on every restart."""
    for i in range(int(ctx.cfg.get("toy_total", 5))):
        if ctx.should_stop():
            return
        ctx.ship(_tagged(ctx, i), env_steps=1)
        if ctx.restart == 0 and i == 1:
            os._exit(3)
        time.sleep(0.01)


def always_crash(ctx):
    """Ship one row then die hard, every generation — quorum-breaker food."""
    ctx.ship(_tagged(ctx, 0), env_steps=1)
    os._exit(3)


def hang(ctx):
    """Send nothing after hello and never ping: heartbeat-timeout food on
    generation 0; `steady` after the supervised restart."""
    if ctx.restart == 0:
        time.sleep(3600.0)
    steady(ctx)


def echo_params(ctx):
    """Wait for the first params broadcast and ship it back verbatim."""
    got = ctx.wait_params(min_version=1, timeout=30.0)
    if got is None:
        return
    version, params = got
    ctx.ship({"replica": ctx.replica, "restart": ctx.restart, "params": params},
             env_steps=1, meta={"version": int(version)})
    # Keep draining ctrl until the supervisor says stop, so a second
    # broadcast (post-restart re-offer assertions) can also be echoed.
    while not ctx.should_stop():
        newer = ctx.wait_params(min_version=version + 1, timeout=0.1)
        if newer is not None:
            version, params = newer
            ctx.ship({"replica": ctx.replica, "restart": ctx.restart, "params": params},
                     env_steps=1, meta={"version": int(version)})
        ctx.maybe_ping()


def ship_until_stopped(ctx):
    """Ship continuously until told to stop — drain_and_stop exercise."""
    i = 0
    while not ctx.should_stop():
        ctx.ship(_tagged(ctx, i), env_steps=1)
        i += 1
        time.sleep(0.005)


def chaos_driven(ctx):
    """Like `steady`, but the per-replica ChaosMonkey (kill9/drop_shipment
    injectors with a matching `replica` key) decides what actually happens
    inside each ship() call."""
    for i in range(int(ctx.cfg.get("toy_total", 5))):
        if ctx.should_stop():
            return
        ctx.ship(_tagged(ctx, i), env_steps=1)
        time.sleep(0.01)
