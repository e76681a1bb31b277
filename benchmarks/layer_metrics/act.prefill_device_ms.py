"""Device time of one execution of the player's prefill (whole prompts through the whole-sequence form): the XLA modules whose name holds `act_prefill`, total time over count."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("modules"):
        return None
    names = [n for n in trace["modules"] if "act_prefill" in n]
    count = sum(trace["module_counts"][n] for n in names)
    return sum(trace["modules"][n] for n in names) * 1e3 / count if count else None
