"""Plain references that decide ``correct``.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``, with no
kernels, cache or batching.  They import nothing of the program and take
nothing it has made: the weights and inputs are the benchmark's own, made
from the seed (``make_weights``, ``make_inputs``).  ``quant="fp8"`` gives
the control: the same arithmetic with every matrix product's operands
rounded to float8 (e4m3, scaled per tensor), the precision below the
configurations' bfloat16.
"""
