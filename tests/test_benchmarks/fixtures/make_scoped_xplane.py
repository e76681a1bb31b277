"""Writes fixtures/scoped.xplane.pb: a hand-made trace with nested operations
(a `while` and what runs inside it), the `op_name` of each operation as the
`tf_op` stat of its event metadata (one has none of the step's scopes), two scopes in both directions, and the program's
own spans on two threads with a known shift to the device's clock. Every
answer is known exactly (times in microseconds below). Needs tensorflow's
xplane protobuf; run by hand, the .pb is committed."""

import os

US = 1_000_000  # picoseconds

J = "jit(train_step)/"
FWD = {s: f"{J}jvp(dv3/{s})/" for s in ("encoder", "rssm", "heads")}
BWD = {s: f"{J}transpose(jvp(dv3/{s}))/" for s in ("rssm", "heads")}


def step_ops(b):
    """One execution of the train step from ``b``: (name, start, duration, op_name)."""
    return [
        ("%fusion.1 = bf16[16,64]{1,0} fusion(%p.1), kind=kLoop", b, 200, FWD["encoder"] + "cnn_encoder/conv_0/conv_general_dilated"),
        # forward scan: the loop keeps what its body's operations do not cover (600 - 400)
        ("%while.2 = (s32[], bf16[16,4096]{1,0}) while(%tuple.1), condition=%c.2, body=%b.2", b + 200, 600, FWD["rssm"] + "while"),
        ("%fusion.3 = bf16[16,4096]{1,0} fusion(%p.3), kind=kOutput", b + 250, 100, FWD["rssm"] + "while/body/recurrent_model/dot_general"),
        ("%fusion.3 = bf16[16,4096]{1,0} fusion(%p.3), kind=kOutput", b + 400, 100, FWD["rssm"] + "while/body/recurrent_model/dot_general"),
        ("%dot.4 = bf16[16,1024]{1,0} dot(%a, %b)", b + 500, 200, FWD["rssm"] + "while/body/transition_model/dot_general"),
        ("%fusion.5 = f32[64,16]{1,0} fusion(%p.5), kind=kLoop", b + 800, 100, FWD["heads"] + "reward_model/dot_general"),
        # 100 idle, then the backward scan with a loop inside the loop
        ("%while.6 = (s32[], f32[5120,12288]{1,0}) while(%tuple.6), condition=%c.6, body=%b.6", b + 1000, 500, BWD["rssm"] + "while"),
        ("%while.7 = (s32[], bf16[16,4096]{1,0}) while(%tuple.7), condition=%c.7, body=%b.7", b + 1050, 300, BWD["rssm"] + "while/body/while"),
        ("%fusion.8 = bf16[16,4096]{1,0} fusion(%p.8), kind=kOutput", b + 1100, 100, BWD["rssm"] + "while/body/while/body/dot_general"),
        ("%select_add_fusion.9 = f32[5120,12288]{1,0} fusion(%p.9), kind=kLoop", b + 1400, 50, BWD["rssm"] + "while/body/add_any"),
        ("%fusion.10 = f32[64,16]{1,0} fusion(%p.10), kind=kLoop", b + 1500, 100, BWD["heads"] + "mul"),
        ("%copy.11 = f32[5120,12288]{0,1} copy(%p.11)", b + 1600, 50, J + "reduce_sum"),
        ("%fusion.12 = f32[5120,12288]{1,0} fusion(%p.12), kind=kLoop", b + 1650, 250, J + "dv3/optim/add"),
    ]


#: per execution of the train step, by (scope, direction); 1800 of its 2000 us busy.
#: A loop counts once: while.2 keeps 200 of its 600, while.6 150, while.7 200.
SELF_US = {("dv3/encoder", "fwd"): 200, ("dv3/rssm", "fwd"): 600, ("dv3/heads", "fwd"): 100, ("dv3/rssm", "bwd"): 500,
           ("dv3/heads", "bwd"): 100, ("unscoped", "fwd"): 50, ("dv3/optim", "fwd"): 250}

MARK = ("%add.0 = f32[8,128]{1,0} add(%x, %y)", None)
DEVICE = {
    "XLA Modules": [("jit_bench_marker(1)", 490, 10, None), ("jit_train_step(123)", 1000, 2000, None), ("jit__player_step(7)", 3500, 100, None),
                    ("jit_train_step(123)", 5000, 2000, None), ("jit_bench_marker(1)", 8500, 10, None)],
    "XLA Ops": [(MARK[0], 490, 10, None), *step_ops(1000), ("%fusion.13 = bf16[1,17]{1,0} fusion(%p.13), kind=kLoop", 3500, 100, "jit(_player_step)/dv3/act/actor/dot_general"),
                *step_ops(5000), (MARK[0], 8500, 10, None)],
}
HOST = {"python": [("unrelated", 0, 9000, None)]}

#: The program's tracer was born at this perf_counter second, and perf_counter
#: read 100.0005 s when the first marker program ended at 500 us on the
#: device's clock: a span is on the device's clock at ts_us - 50 s.
PERF_EPOCH_S = 50.0
HOST_CLOCK_AT_FIRST_MARKER = 100.0005
MAIN, WORKER = "MainThread", "sheeprl-infeed_0"
#: (name, thread, start and end in us on the device's clock, args)
SPANS = [
    ("fetch/player_actions", MAIN, 100, 400, {}),  # before the window
    ("loop/iteration", MAIN, 600, 4000, {"step": 1, "gradient_steps": 1}),
    ("Time/env_interaction_time", MAIN, 620, 3700, {}),  # holds others: not a leaf
    ("interaction/dispatch/slice0", MAIN, 650, 700, {}),
    ("fetch/player_actions", MAIN, 3000, 3450, {}),
    ("interaction/env_step/slice0", MAIN, 3460, 3490, {}),
    ("replay/add", MAIN, 3600, 3650, {}),
    ("infeed/take", MAIN, 3700, 3720, {"hit": True}),
    ("train/dispatch", MAIN, 3720, 3900, {}),
    ("transfer/h2d_stage", WORKER, 3950, 4600, {"batches": 1}),  # hidden behind the device: not the loop's wait
    ("loop/iteration", MAIN, 4000, 8400, {"step": 2, "gradient_steps": 1}),
    ("infeed/take", MAIN, 4100, 4110, {"hit": False}),
    ("transfer/h2d_sync", MAIN, 4110, 4400, {"batches": 1}),  # holds replay/sample: not a leaf
    ("replay/sample", MAIN, 4120, 4200, {}),
    ("train/dispatch", MAIN, 4500, 4600, {}),
    ("fetch/player_actions", MAIN, 7000, 8300, {}),
    ("interaction/env_step/slice0", MAIN, 8310, 8350, {}),
]
#: idle 4300 us of the 8000 us window; under leaf spans of the loop thread:
IDLE_US, IDLE_COVERED_US = 4300, 50 + 450 + 30 + 50 + 20 + 180 + 10 + 80 + 100 + 1300 + 40


def telemetry_records():
    """The spans as the program's telemetry.jsonl holds them."""
    shift_us = (HOST_CLOCK_AT_FIRST_MARKER - 0.0005 - PERF_EPOCH_S) * 1e6
    records = [{"type": "meta", "perf_epoch_s": PERF_EPOCH_S, "wall_epoch_s": 1.7e9}]
    for name, thread, start, end, args in SPANS:
        records.append({"type": "span", "name": name, "cat": "x", "ts_us": start + shift_us, "dur_us": end - start,
                        "thread": thread, **({"args": args} if args else {})})
    return records


def add_plane(space, name, lines):
    plane = space.planes.add()
    plane.name = name
    plane.stat_metadata[1].id = 1
    plane.stat_metadata[1].name = "tf_op"
    plane.stat_metadata[2].id = 2
    plane.stat_metadata[2].name = "flops"
    ids = {}
    for line_id, (line_name, events) in enumerate(lines.items()):
        line = plane.lines.add()
        line.id = line_id
        line.name = line_name
        line.timestamp_ns = 0
        for event_name, start_us, dur_us, op_name in events:
            if event_name not in ids:
                ids[event_name] = len(ids) + 1
                meta = plane.event_metadata[ids[event_name]]
                meta.id = ids[event_name]
                meta.name = event_name
                flops = meta.stats.add()
                flops.metadata_id = 2
                flops.uint64_value = 1024
                if op_name is not None:
                    stat = meta.stats.add()
                    stat.metadata_id = 1
                    stat.str_value = op_name
            event = line.events.add()
            event.metadata_id = ids[event_name]
            event.offset_ps = start_us * US
            event.duration_ps = dur_us * US


if __name__ == "__main__":
    # only here: the tests import this module for its constants, and loading
    # tensorflow beside JAX in a test worker can take the worker down
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    add_plane(space, "/device:TPU:0", DEVICE)
    add_plane(space, "/host:CPU", HOST)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "scoped.xplane.pb"), "wb") as fp:
        fp.write(space.SerializeToString())
