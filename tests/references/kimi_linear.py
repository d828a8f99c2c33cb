"""The plain float32 reference of ``gluon.model_zoo.nlp.kimi_linear``, for the
tier-1 tests.  There is one copy of it, the benchmark's
(``benchmark/reference/kimi_linear.py``); this module loads that file by its
path as ``benchmark_copy``."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_reference_kimi_linear",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                 os.pardir, "benchmark", "reference", "kimi_linear.py"))
benchmark_copy = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(benchmark_copy)

logits = benchmark_copy.logits
loss = benchmark_copy.loss
gradient_program = benchmark_copy.gradient_program
layer_parameters = benchmark_copy.layer_parameters
delta_rule = benchmark_copy.delta_rule
causal_conv = benchmark_copy.causal_conv
mla = benchmark_copy.mla
ds = benchmark_copy.ds
