"""Device milliseconds per batch of the feed-forward sub-layers: the leaf
operations of the traced window under the program's ``ffn`` scope (norm,
MLP or MoE, residual), over the batches the window folded, averaged over the
chips. An operation's scope is its HLO ``op_name``'s; a fusion takes the
scope of its root instruction (``bench.trace``). Nothing where no operation
carries the scope, as in a backbone with no feed-forward sub-layer."""


def read(run):
    sec = run.trace.scope_seconds("ffn")
    if sec <= 0 or not run.facts["steps"]:
        return None
    return sec / run.facts["steps"] * 1e3
