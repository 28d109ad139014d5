"""Names of ``serving.Engine``'s jitted programs on the ``XLA Modules`` line
(a jitted function ``f`` runs as ``jit_f(<hash>)``)."""
DECODE = "jit_decode("
PREFILL = "jit_prefill("
CHUNK_PREFILL = "jit_chunk("
ALL = (DECODE, PREFILL, CHUNK_PREFILL)
