"""Operations one gradient step of the toy policy needs: the two matrix
products forward, and twice as many backward (by input and by weight), two
operations per multiply-add, over the rollout's rows."""


def step_flops(model):
    rows = model["rows"]
    forward = 2.0 * rows * (model["obs"] * model["hidden"] + model["hidden"] * model["actions"])
    return {"policy": 3.0 * forward, "total": 3.0 * forward}
