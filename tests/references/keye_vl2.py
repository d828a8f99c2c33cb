"""The plain float32 reference of ``gluon.model_zoo.nlp.keye_vl2``, for the
tier-1 tests.  There is one copy of it, the benchmark's
(``benchmark/reference/keye_vl2.py``); this module loads that file by its
path as ``benchmark_copy``."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_reference_keye_vl2",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                 os.pardir, "benchmark", "reference", "keye_vl2.py"))
benchmark_copy = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(benchmark_copy)

forward = benchmark_copy.forward
loss = benchmark_copy.loss
gradient_program = benchmark_copy.gradient_program
router = benchmark_copy.router
experts = benchmark_copy.experts
layer_parameters = benchmark_copy.layer_parameters
selection = benchmark_copy.selection
control = benchmark_copy.control
