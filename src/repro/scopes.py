"""The names the client's local stage gives its layers in a profiler trace.

Device scopes are ``jax.named_scope`` names. Each is opened inside a function
that ``jit`` traces, so it reaches the compiled HLO's
``metadata={op_name=...}`` of every operation under it (a scope opened
around a call of an already-jitted function from the host names nothing).
An operation's op name is the path of the scopes open where it was traced,
e.g. ``jit(fwd)/while/body/closed_call/mixer/seqmix/while/body/...``.

Host spans are ``jax.profiler.TraceAnnotation`` names, on the device
trace's clock. Scopes are compile-time metadata and change no arithmetic;
spans cost about a microsecond each. Both are always on.
"""

# -- device scopes: the backbone forward (``train._embed_fn``) -------------
EMBED = "embed"            # the token lookup
MIXER = "mixer"            # the mixer sub-layer: norm, projections, mixing
SEQMIX = "seqmix"          # inside MIXER: attention core or time recurrence
FFN = "ffn"                # the feed-forward sub-layer: norm and MLP/MoE
FINAL_NORM = "final_norm"  # the last norm of the stack
POOL = "pool"              # the sequence mean

# -- device scopes: the Gram fold -------------------------------------------
GRAM_FOLD = "gram_fold"    # the jitted Pallas Gram update: pad, kernel, slices
GRAM_PSUM = "gram_psum"    # the all-reduce that joins the chips' statistics

# -- host spans: the client ------------------------------------------------
FOLD_SPAN = "afl.fold"            # AFLClient.update, start to end
FOLD_ROOT_SPAN = "afl.fold.root"  # its host copy of a batch below d rows
