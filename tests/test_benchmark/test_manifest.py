"""The manifest's self-check, and that a later PR can add a configuration, a
traffic mix, a per-layer metric and a cell as files and entries only."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark.harness.manifest import Manifest, ManifestError

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture()
def copy(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def _edit(root, fn):
    data = json.loads((root / "BENCHMARK.json").read_text())
    fn(data)
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    return Manifest(root)


def test_committed_manifest_passes_and_names_only_files_that_exist():
    m = Manifest(ROOT)
    m.check()
    assert set(m.data) == {"command", "paths", "run_seconds", "configs", "workloads",
                           "end_to_end", "per_layer"}
    for w in m.data["workloads"]:
        cell = m.cell(w["name"])
        assert (ROOT / cell.config["reference"]).exists()
        assert m.generator_file(cell.traffic["kind"]).exists()
        assert len(w["why"]) <= 200
    for c in m.data["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]


def test_every_layer_metric_lives_only_beside_the_metric_it_moves():
    m = Manifest(ROOT)
    e2e = {x["name"]: x for x in m.data["end_to_end"]}
    for metric in m.data["per_layer"]:
        moved = e2e[metric["moves"]]
        for cell in metric["workloads"]:
            assert "workloads" not in moved or cell in moved["workloads"], metric["name"]


def test_each_cell_has_an_mfu_named_share_beside_its_kernel_rooflines():
    m = Manifest(ROOT)
    for w in m.data["workloads"]:
        layer = m.metrics_of("per_layer", w["name"])
        mfu = {x["moves"] for x in layer if "mfu" in x["name"].split(".")[-1].split("_")}
        assert mfu, w["name"]
        for x in layer:
            if x["name"].endswith("_roofline"):
                assert x["unit"] == "%" and x["moves"] in mfu


@pytest.mark.parametrize("fault, expect", [
    (lambda d: [m for m in d["per_layer"] if m["name"] == "train.step_mfu"][0]["workloads"].append("serve-mistral7b-docqa"),
     "which it should move, is not"),
    (lambda d: d["per_layer"][0].update(unit="tokens per second"), "unit"),
    (lambda d: d["per_layer"][0].update(unit="a" * 17), "unit"),
    (lambda d: d["workloads"][0].update(name="bad name"), "character"),
    (lambda d: d["workloads"][0].update(traffic="no-such-mix"), "missing file"),
    (lambda d: d["workloads"][0].update(config="no-such-config"), "unknown configuration"),
    (lambda d: [w.update(chips=4) for w in d["workloads"][:2]], "more than a quarter"),
    (lambda d: d["configs"].append(dict(d["configs"][0], name="idle", file="benchmark/configs/idle.json")),
     "has no cell"),
    (lambda d: d["end_to_end"][0].update(bound=0.2), "bound"),
], ids=["pr22_metric_beside_wrong_cell", "unit_with_space", "unit_too_long", "name_with_space",
        "traffic_file_missing", "config_unknown", "too_many_four_chip_cells",
        "config_without_cell", "bound_over_a_tenth"])
def test_self_check_refuses(copy, fault, expect):
    with pytest.raises(ManifestError, match=expect):
        _edit(copy, fault).check()


def test_missing_reader_and_generator_are_refused(copy):
    (copy / "benchmark/readers/trace_idle.py").unlink()
    with pytest.raises(ManifestError, match="no reader"):
        Manifest(copy).check()


def _add_the_worked_example(root):
    """benchmark/README.md's worked example, in the copy at ``root``: a
    configuration, a traffic mix, a served cell appended to every list that
    holds ``serve-mistral7b-docqa``, and a per-layer metric at the END of
    ``per_layer``."""
    cfg = json.loads((root / "benchmark/configs/mistral-7b-v0.3-serve.json").read_text())
    cfg.update(name="mistral-7b-v0.3-serve-long", engine=dict(cfg["engine"], seq=32768),
               reference="benchmark/configs/mistral-7b-v0.3-serve-long_reference.py")
    (root / "benchmark/configs/mistral-7b-v0.3-serve-long.json").write_text(json.dumps(cfg))
    shutil.copy(root / "benchmark/configs/mistral-7b-v0.3-serve_reference.py",
                root / "benchmark/configs/mistral-7b-v0.3-serve-long_reference.py")
    mix = json.loads((root / "benchmark/traffic/docqa-closed8.json").read_text())
    mix.update(name="docqa-unshared", questions_per_document=1)
    (root / "benchmark/traffic/docqa-unshared.json").write_text(json.dumps(mix))
    (root / "benchmark/layer_metrics/serve.closed_ttft_p50_ms.json").write_text(json.dumps({
        "name": "serve.closed_ttft_p50_ms", "layer": "serving engine", "unit": "ms",
        "better": "lower", "source": "host_clock", "moves": "serve_tokens_per_s",
        "reader": "client_percentile", "args": {"field": "ttft_ms", "q": 50}}))

    def add(d):
        d["configs"].append({"name": "mistral-7b-v0.3-serve-long", "source": cfg["source"],
                             "file": "benchmark/configs/mistral-7b-v0.3-serve-long.json",
                             "reduced": cfg["reduced"], "why": "longer contexts"})
        d["workloads"].append({"name": "serve-mistral7b-unshared",
                               "config": "mistral-7b-v0.3-serve-long",
                               "traffic": "docqa-unshared", "chips": 1,
                               "why": "no reuse: the control for a prefix-cache claim"})
        for m in d["end_to_end"] + d["per_layer"]:
            if "serve-mistral7b-docqa" in m.get("workloads", []):
                m["workloads"].append("serve-mistral7b-unshared")
        d["per_layer"].append({"name": "serve.closed_ttft_p50_ms", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "serving engine", "moves": "serve_tokens_per_s",
                               "workloads": ["serve-mistral7b-unshared"]})

    return _edit(root, add)


def test_a_later_pr_adds_files_and_entries_only(copy):
    """The worked example of benchmark/README.md: nothing that was there is edited."""
    before = {p: p.read_bytes() for p in (copy / "benchmark").rglob("*") if p.is_file()}
    m = _add_the_worked_example(copy)
    m.check()
    cell = m.cell("serve-mistral7b-unshared")
    assert cell.config["engine"]["seq"] == 32768 and cell.traffic["questions_per_document"] == 1
    assert "serve.closed_ttft_p50_ms" in {x["name"] for x in cell.per_layer}
    assert all(p.read_bytes() == b for p, b in before.items())


#: The tests that read the manifest as committed: each configuration's own
#: entries, the metrics that every served cell reports, the self-check.
MANIFEST_READERS = [
    "test_manifest.py::test_committed_manifest_passes_and_names_only_files_that_exist",
    "test_manifest.py::test_every_layer_metric_lives_only_beside_the_metric_it_moves",
    "test_manifest.py::test_each_cell_has_an_mfu_named_share_beside_its_kernel_rooflines",
    "test_loop_instruments.py::test_the_file_agrees_with_the_manifest_and_lists_the_served_cells",
    *(f"test_{name}_config.py::test_the_new_entries_load_and_the_references_name_escapes_the_dense_glob"
      for name in ("hybrid", "latent_moe", "window_moe")),
]


def test_a_cell_added_as_files_and_entries_leaves_the_benchmarks_own_tests_passing(copy):
    """The worked example, and then every test that reads the manifest, run on
    the copy: none pins how many cells there are, what else a shared list
    holds, or where an entry stands."""
    shutil.copytree(ROOT / "tests/test_benchmark", copy / "tests/test_benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (copy / "tests/__init__.py").write_text("")
    _add_the_worked_example(copy).check()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         *(f"tests/test_benchmark/{t}" for t in MANIFEST_READERS)],
        capture_output=True, text=True, timeout=120, cwd=copy,
        env={**os.environ, "PYTHONPATH": os.pathsep.join([str(copy), str(ROOT)])})
    assert proc.returncode == 0, proc.stdout[-3000:]
    assert "13 passed" in proc.stdout.splitlines()[-1], proc.stdout[-500:]
