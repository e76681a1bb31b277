"""Device time of one execution of the train-step program, from the trace:
the XLA modules whose name holds one of the configuration's
``train_modules``, total time over count."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("modules"):
        return None
    wanted = run["cell"].config["program"]["train_modules"]
    names = [n for n in trace["modules"] if any(w in n for w in wanted)]
    count = sum(trace["module_counts"][n] for n in names)
    if not count:
        return None
    return sum(trace["modules"][n] for n in names) * 1e3 / count
