"""The plain float32 reference of ``gluon.model_zoo.nlp.deepseek_v3``, for
the tier-1 tests.  There is one copy of it, the benchmark's
(``benchmark/reference/deepseek_v3.py``: a configuration's reference lives
under the benchmark's own directory).  This module loads that file by its
path as ``benchmark_copy`` and hands on its functions, with plain
``causal_attention`` where the benchmark takes a block of heads at a time."""
import functools
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_reference_deepseek_v3",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                 os.pardir, "benchmark", "reference", "deepseek_v3.py"))
benchmark_copy = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(benchmark_copy)

causal_attention = benchmark_copy.causal_attention
rope_angles = benchmark_copy.rope_angles
rope_interleaved = benchmark_copy.rope_interleaved
rope_permute_then_rotate_halves = \
    benchmark_copy.rope_permute_then_rotate_halves
swiglu = benchmark_copy.swiglu
router = benchmark_copy.router
routed_experts = benchmark_copy.routed_experts
moe = benchmark_copy.moe
logits = functools.partial(benchmark_copy.logits,
                           attention=causal_attention)
loss = functools.partial(benchmark_copy.loss, attention=causal_attention)
