"""Device milliseconds per batch of the backbone forward with pooling: the
runs of the forward's jitted program (``train._embed_fn``) in the traced
window, over the batches the window folded."""


def read(run):
    program = run.facts["forward_program"]
    sec = run.trace.module_seconds(lambda name: name.split("(")[0] == program)
    if sec <= 0 or not run.facts["steps"]:
        return None
    return sec / run.facts["steps"] * 1e3
