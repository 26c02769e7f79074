"""Device milliseconds per batch of the all-reduce that joins the chips'
Gram statistics on a mesh (the ``lax.psum`` of ``kernels/ops``: the
``%all-reduce`` operations of the traced window), averaged over the chips.
Nothing where no operation all-reduces, as on one chip."""

from bench import trace


def _is_all_reduce(op_name: str) -> bool:
    return trace.short(op_name).lstrip("%").startswith("all-reduce")


def read(run):
    sec = run.trace.op_seconds(_is_all_reduce)
    if sec <= 0 or not run.facts["steps"]:
        return None
    return sec / run.facts["steps"] * 1e3
