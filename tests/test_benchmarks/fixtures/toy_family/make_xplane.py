"""Writes toy.xplane.pb: two executions of `jit_toy_step` between the two
marker programs, its operations under the scopes `toy/policy` (both
directions) and `toy/optim`, one under none. Every answer is known (times in
microseconds). Needs tensorflow's xplane protobuf; run by hand from
tests/test_benchmarks/fixtures, the .pb is committed."""

import os
import sys

J = "jit(toy_step)/"


def step_ops(b):
    return [
        ("%fusion.1 = f32[8,16]{1,0} fusion(%p.1), kind=kOutput", b, 100, J + "jvp(toy/policy)/dot_general"),
        ("%fusion.2 = f32[8,4]{1,0} fusion(%p.2), kind=kOutput", b + 100, 50, J + "jvp(toy/policy)/log_softmax"),
        ("%fusion.3 = f32[16,4]{1,0} fusion(%p.3), kind=kOutput", b + 150, 120, J + "transpose(jvp(toy/policy))/dot_general"),
        ("%copy.4 = f32[8,16]{0,1} copy(%p.4)", b + 270, 30, J + "transpose"),
        ("%fusion.5 = f32[8,16]{1,0} fusion(%p.5), kind=kLoop", b + 300, 100, J + "toy/optim/sub"),
    ]


#: per execution of the step (410 us on the device), by (scope, direction): 400 us busy
SELF_US = {("toy/policy", "fwd"): 150, ("toy/policy", "bwd"): 120, ("unscoped", "fwd"): 30, ("toy/optim", "fwd"): 100}
STEPS = 2
MARK = "%add.0 = f32[8,128]{1,0} add(%x, %y)"
DEVICE = {
    "XLA Modules": [("jit_bench_marker(1)", 490, 10, None), ("jit_toy_step(5)", 1000, 410, None), ("jit_toy_step(5)", 2000, 410, None),
                    ("jit_bench_marker(1)", 3500, 10, None)],
    "XLA Ops": [(MARK, 490, 10, None), *step_ops(1000), *step_ops(2000), (MARK, 3500, 10, None)],
}
WINDOW_US = 3000  # between the first marker's end and the last one's start

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from make_scoped_xplane import add_plane
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    add_plane(space, "/device:TPU:0", DEVICE)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy.xplane.pb"), "wb") as fp:
        fp.write(space.SerializeToString())
