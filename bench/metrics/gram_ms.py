"""Device milliseconds per batch of the Gram fold: every program of the
traced window other than the forward — the Pallas Gram kernel, the
accumulation into the client's statistics and, on a mesh, the psum that
joins the chips' partial statistics."""


def read(run):
    program = run.facts["forward_program"]
    sec = run.trace.module_seconds(lambda name: name.split("(")[0] != program)
    if sec <= 0 or not run.facts["steps"]:
        return None
    return sec / run.facts["steps"] * 1e3
