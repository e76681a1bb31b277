"""Writes fixtures/small.xplane.pb: a hand-made trace whose busy union, idle
gaps and per-program device time are known exactly (times in microseconds
below). Needs tensorflow's xplane protobuf; run by hand, the .pb is committed."""

import os

US = 1_000_000  # picoseconds

DEVICE = {
    "XLA Modules": [("jit_bench_marker(1)", 490, 10), ("jit_train_step(123)", 1000, 2000), ("jit__player_step(7)", 3500, 100),
                    ("jit_train_step(123)", 5000, 2000), ("jit_bench_marker(1)", 8500, 10)],
    "XLA Ops": [("add.0", 490, 10), ("fusion.1", 1000, 1000), ("fusion.2", 1900, 1100), ("copy.3", 3500, 100),
                ("fusion.1", 5000, 2000), ("add.0", 8500, 10)],
    "Steps": [("0", 1000, 6000)],
}
HOST = {"python": [("unrelated", 0, 9000)]}
#: What the harness's own wrappers stamped on the host clock, which read
#: 100.0005 s when the first marker program ended at 500 us on the device's.
HOST_CLOCK_AT_FIRST_MARKER = 100.0005
HOST_STAMPS = [("bench/env_step", 100.003, 100.0034), ("bench/action_fetch", 100.0036, 100.005),
               ("bench/train_dispatch", 100.007, 100.0072)]


def add_plane(space, name, lines):
    plane = space.planes.add()
    plane.name = name
    ids = {}
    for line_id, (line_name, events) in enumerate(lines.items()):
        line = plane.lines.add()
        line.id = line_id
        line.name = line_name
        line.timestamp_ns = 0
        for event_name, start_us, dur_us in events:
            if event_name not in ids:
                ids[event_name] = len(ids) + 1
                plane.event_metadata[ids[event_name]].id = ids[event_name]
                plane.event_metadata[ids[event_name]].name = event_name
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
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "small.xplane.pb"), "wb") as fp:
        fp.write(space.SerializeToString())
