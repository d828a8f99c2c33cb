"""The plain float32 reference of ``gluon.model_zoo.nlp.sdar_moe`` under
the block-diffusion objective, for the tier-1 tests.  There is one copy of
it, the benchmark's (``benchmark/reference/sdar_moe.py``); this module loads
that file by its path as ``benchmark_copy``."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_reference_sdar_moe",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                 os.pardir, "benchmark", "reference", "sdar_moe.py"))
benchmark_copy = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(benchmark_copy)

loss = benchmark_copy.loss
gradient_program = benchmark_copy.gradient_program
layer_parameters = benchmark_copy.layer_parameters
experts = benchmark_copy.experts
visible = benchmark_copy.visible
control = benchmark_copy.control
