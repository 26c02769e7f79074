"""Device milliseconds per batch of sequence mixing: the leaf operations of
the traced window under the program's ``mixer/seqmix`` scope (the attention
core, or the mLSTM/sLSTM recurrence), over the batches the window folded,
averaged over the chips. An operation's scope is its HLO ``op_name``'s; a
fusion takes the scope of its root instruction (``bench.trace``). Nothing
where no operation carries the scope."""


def read(run):
    sec = run.trace.scope_seconds("mixer/seqmix")
    if sec <= 0 or not run.facts["steps"]:
        return None
    return sec / run.facts["steps"] * 1e3
