"""Work of one round on an encoder: a dense block without the causal mask,
which ``dense`` reads from the configuration (``causal``, ``gated_mlp``)."""
from bench.flops.dense import round_work, step_work  # noqa: F401
