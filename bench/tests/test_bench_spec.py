"""Everything ``BENCHMARK.json`` names resolves to a file of its own, and a
new file with a new entry is found without editing any existing file.
Imports nothing that touches a chip."""
import json
import re
import shutil

import pytest

from bench.spec import ROOT, Spec

SPEC = Spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_config_resolves():
    for c in SPEC.data["configs"]:
        path = SPEC.config_path(c["name"])
        assert path.is_file()
        assert path.resolve().is_relative_to(ROOT / "bench")
        data = SPEC.config(c["name"])
        dims = SPEC.arch_module(data["model_type"]).load_dims(data)
        assert dims.name == c["name"]
        assert set(c["reduced"]) == set(data["reduced"])


@pytest.mark.parametrize("w", SPEC.data["workloads"], ids=lambda w: w["name"])
def test_every_workload_resolves(w):
    mix = SPEC.traffic(w["traffic"])
    assert mix["protocol"] == "minions"
    assert SPEC.config_path(w["config"]).is_file()
    limits = SPEC.limits(w["name"])
    assert limits["max_logit_gap"]["limit"] > 0
    assert w["chips"] in (1, 4)


@pytest.mark.parametrize("m", SPEC.data["end_to_end"] + SPEC.data["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(m):
    mod = SPEC.metric_module(m["name"])
    assert callable(mod.read)


def test_contract_shape():
    d = SPEC.data
    assert d["command"][1] == "bench/run.py" and d["paths"] == ["bench"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in d[k]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    e2e = {m["name"] for m in d["end_to_end"]}
    assert "setup_s" in e2e
    for m in d["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in d["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in d["end_to_end"] + d["per_layer"]:
        assert m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    assert len(json.dumps(d)) < 64 * 1024


def test_new_files_are_found_by_name(tmp_path):
    """A later cell brings its own config, architecture, mix, metric and
    limits: new files and entries only."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/yi-6b.json").read_text())
    cfg["name"] = "yi-6b-short"
    (tmp_path / "bench/configs/yi-6b-short.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/configs/toy.json").write_text(json.dumps(
        dict(cfg, name="toy", model_type="toy")))
    (tmp_path / "bench/arch/toy.py").write_text(
        "import dataclasses\n\n\n"
        "@dataclasses.dataclass(frozen=True)\n"
        "class Dims:\n    name: str\n    attn_layers: int\n\n\n"
        "def load_dims(config):\n"
        "    return Dims(config['name'], 3)\n")
    (tmp_path / "bench/traffic/short.json").write_text(json.dumps(
        dict(SPEC.traffic("fanout"), document_pages=4)))
    (tmp_path / "bench/metrics/queue_wait_s.py").write_text(
        "def read(ctx):\n    return 1.5\n")
    (tmp_path / "bench/limits/yi-6b-short.short.json").write_text(
        json.dumps({"max_logit_gap": {"limit": 0.5}}))
    for name in ("yi-6b-short", "toy"):
        data["configs"].append({"name": name, "source": "x",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "x"})
    data["workloads"].append({"name": "yi-6b-short.short",
                              "config": "yi-6b-short", "traffic": "short",
                              "chips": 1, "why": "x"})
    data["per_layer"].append({"name": "queue_wait_s", "unit": "s",
                              "better": "lower", "source": "program_span",
                              "layer": "x", "moves": "tasks_per_s",
                              "workloads": ["yi-6b-short.short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))

    spec = Spec(tmp_path)
    short = spec.config("yi-6b-short")
    assert spec.arch_module(short["model_type"]).load_dims(short).name == \
        "yi-6b-short"
    toy = spec.config("toy")
    assert spec.arch_module(toy["model_type"]).load_dims(toy).attn_layers == 3
    assert spec.traffic("short")["document_pages"] == 4
    assert spec.limits("yi-6b-short.short")["max_logit_gap"]["limit"] == 0.5
    assert spec.metric_module("queue_wait_s").read(None) == 1.5
    new = [m["name"] for m in spec.metrics_for("yi-6b-short.short", True)]
    old = [m["name"] for m in spec.metrics_for("yi-6b.fanout", True)]
    assert "queue_wait_s" in new and "queue_wait_s" not in old
    assert [m["name"] for m in spec.metrics_for("yi-6b.fanout", False)] == \
        ["tasks_per_s", "setup_s"]


def test_unknown_names_fail_loudly():
    with pytest.raises(KeyError):
        SPEC.workload("no-such-cell")
    with pytest.raises(KeyError):
        SPEC.config_path("no-such-config")
    with pytest.raises(FileNotFoundError):
        SPEC.metric_module("no_such_metric")
    with pytest.raises(FileNotFoundError, match="no_such_arch.py"):
        SPEC.arch_module("no_such_arch")


def test_peak_table_is_keyed_by_device_kind():
    from bench.harness import peak_for
    assert peak_for("TPU v5 lite", SPEC)["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peak_for("TPU v9 imaginary", SPEC)
