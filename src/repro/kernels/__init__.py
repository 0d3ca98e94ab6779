"""Pallas TPU kernels for the framework's compute hot spots.

- gram_update:     fused border-eval + tall-skinny Gram (OAVI hot loop)
- ihb_update:      Theorem 4.9 block-inverse update
- svm_grad:        the linear SVM's squared-hinge gradient, one pass over X
- flash_attention: blocked causal GQA attention (LM substrate)

``ops`` holds the public jit wrappers (with jnp fallback on non-TPU
backends); ``ref`` holds the pure-jnp oracles the tests compare against.
"""

from . import ops, ref

__all__ = ["ops", "ref"]
