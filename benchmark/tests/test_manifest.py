"""The manifest loader refuses what the harness or the driver would refuse.
Pure Python, CPU: ``pytest benchmark/tests`` (tier-1 collects ``tests/``)."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

import manifest  # noqa: E402


def test_committed_manifest_is_valid_and_every_cell_loads():
    man = manifest.Manifest().validate()
    assert man.workloads and "setup_s" in man.end_to_end
    for name in man.workloads:
        cell = man.cell(name)
        assert cell.layer_metrics, name
        assert cell.config["model"] and cell.traffic["runner"]
    four = [w for w in man.doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(man.doc["workloads"]) // 4)


def test_every_reader_named_by_a_metric_exists():
    man = manifest.Manifest()
    for name in man.per_layer:
        assert callable(manifest.reader(manifest.layer_metric(name)))


def test_unknown_device_kind_is_an_error_not_a_default():
    assert manifest.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(manifest.ManifestError, match="TPU v9 imaginary"):
        manifest.peaks("TPU v9 imaginary")
    # no substring match either
    with pytest.raises(manifest.ManifestError):
        manifest.peaks("TPU v5")


def _copy_benchmark(tmp_path, edit):
    """A root with BENCHMARK.json edited by ``edit(doc)``."""
    doc = json.load(open(os.path.join(manifest.ROOT, "BENCHMARK.json")))
    edit(doc)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    os.symlink(manifest.HERE, tmp_path / "benchmark")
    return manifest.Manifest(str(tmp_path))


def test_unknown_metric_name_in_a_traffic_file_is_refused(tmp_path,
                                                          monkeypatch):
    man = manifest.Manifest()
    real = man.traffic

    def traffic(name):
        t = dict(real(name))
        t["layer_metrics"] = t["layer_metrics"] + ["no.such_metric"]
        return t
    monkeypatch.setattr(man, "traffic", traffic)
    with pytest.raises(manifest.ManifestError, match="no.such_metric"):
        man.cell(next(iter(man.workloads)))


def test_metric_listed_for_a_cell_that_does_not_report_it(tmp_path):
    def edit(doc):
        # claim that every cell reports the flash kernel's time
        for m in doc["per_layer"]:
            m.pop("workloads", None)
    man = _copy_benchmark(tmp_path, edit)
    with pytest.raises(manifest.ManifestError, match="does not"):
        man.validate()


@pytest.mark.parametrize("bad", ["tokens per s", "a,b", "a/b", "", ".x",
                                 "x" * 65, "µs"])
def test_names_the_driver_refuses(bad):
    with pytest.raises(manifest.ManifestError):
        manifest.check_name("metric", bad)


@pytest.mark.parametrize("bad", ["tokens per second", "µs", "",
                                 "x" * 17, "a,b"])
def test_units_the_driver_refuses(bad):
    with pytest.raises(manifest.ManifestError):
        manifest.check_unit("metric", bad)


@pytest.mark.parametrize("ok", ["samples/s", "ms/step", "%", "GB", "us"])
def test_units_the_driver_takes(ok):
    assert manifest.check_unit("metric", ok) == ok


def test_bad_name_in_benchmark_json_is_refused(tmp_path):
    def edit(doc):
        doc["end_to_end"][0]["name"] = "samples per s"
    with pytest.raises(manifest.ManifestError):
        _copy_benchmark(tmp_path, edit).validate()
