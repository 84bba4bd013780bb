"""BENCHMARK.json against its contract, every cell resolved by name, every
configuration naming a family that builds it, and a new cell added from
data files alone."""
import json
import math
import os
import re
import shutil

import pytest

import benchkit

import run

with open(os.path.join(benchkit.REPO, "BENCHMARK.json")) as _fh:
    MANIFEST = json.load(_fh)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_shape():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for p in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(benchkit.REPO, p))
    names = [c["name"] for c in MANIFEST["configs"]]
    assert len(set(names)) == len(names)
    assert {w["config"] for w in MANIFEST["workloads"]} == set(names)
    assert len(set(CELLS)) == len(CELLS)
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) \
        <= max(1, len(CELLS) // 2)
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]


# What the reference reads from a mix's `exchange` key, against what the
# launcher's parser makes of its flags: kind, compressor, error feedback.
EXCHANGES = {"single": ("sim", "qsgd8_block1024", True),
             "two_phase": ("two_phase", "qsgd8_block1024", True),
             "exact": ("exact", "identity", False)}


@pytest.mark.parametrize("traffic", sorted(
    f[:-5] for f in os.listdir(os.path.join(benchkit.BENCH, "traffic"))))
def test_exchange_agrees_with_flags(traffic):
    from program import parse_strategy

    t = run.load_json(os.path.join(benchkit.BENCH, "traffic",
                                   traffic + ".json"))
    s = parse_strategy(t["flags"], ("data",) if t["workers"] > 1 else ())
    assert (t["exchange"] == "single") == (t["workers"] == 1)
    assert EXCHANGES[t["exchange"]] == (s.exchange.kind,
                                        s.compression.compressor,
                                        s.compression.error_feedback)
    if t["exchange"] != "exact":
        assert s.compression.plan == "uniform"


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    spec = run.resolve(benchkit.REPO, cell)
    assert spec["traffic"]["workers"] == spec["cell"]["chips"]
    limits = set(spec["limits"])
    assert {"loss_gap", "update1_gap", "change3_gap",
            "window_compiles", "window_nonfinite"} <= limits
    assert limits <= {"loss_gap", "loss1_gap", "loss_mid_gap", "update1_gap",
                      "change3_gap", "change3_dir", "window_compiles",
                      "window_nonfinite"}
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2 and spec["per_layer"]
    for m in spec["per_layer"]:
        assert callable(run.reader(spec["metrics_dir"], m["name"]))


@pytest.mark.parametrize("config", [c["file"] for c in MANIFEST["configs"]])
def test_config_sizes(config):
    """The family's parameter sizes are those of the model the program
    builds from the configuration, and add up to its stated count."""
    import jax

    from repro.models import build

    path = os.path.join(benchkit.REPO, config)
    cfg = run.load_json(path)
    fam = run.load_family(benchkit.REPO, cfg, path)
    shapes = jax.eval_shape(build(fam.program_config(cfg)).init,
                            jax.random.key(0))
    sizes = [math.prod(x.shape) for x in jax.tree.leaves(shapes)]
    assert sizes == fam.param_sizes(cfg)
    assert sum(sizes) == fam.n_params(cfg) == cfg["n_params"]


def _checkout(tmp_path):
    """A checkout at `tmp_path` holding the manifest and `bench/`."""
    root = tmp_path / "checkout"
    shutil.copytree(benchkit.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(benchkit.REPO, "BENCHMARK.json"), root)
    return root


@pytest.mark.parametrize("family", [None, "no_such_family"])
def test_config_family_required(tmp_path, family):
    """A configuration with no `family` key, or naming none that is in
    `bench/families/`, is refused, naming the file and the families
    found."""
    root = _checkout(tmp_path)
    path = root / "bench/configs/dcgan32.json"
    cfg = json.loads(path.read_text())
    cfg.pop("family")
    if family is not None:
        cfg["family"] = family
    path.write_text(json.dumps(cfg))
    with pytest.raises(run.BenchError) as e:
        run.resolve(str(root), "dcgan32.q8.b64")
    msg = str(e.value)
    assert str(path) in msg and "['dcgan']" in msg
    assert ("no \"family\" key" if family is None else repr(family)) in msg


def test_new_cell_from_data_files_alone(tmp_path):
    """A cell with its own configuration, traffic mix and limits, added as
    files and a manifest entry, runs at smoke size with no code edit."""
    import jax

    root = _checkout(tmp_path)
    manifest = json.loads(json.dumps(MANIFEST))
    cfg = json.loads((root / "bench/configs/dcgan32.json").read_text())
    cfg = run.load_family(str(root), cfg, "dcgan32.json").smoke(cfg)
    cfg["name"] = "tiny"
    cfg["gan_config"]["name"] = "tiny"
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    traffic = json.loads((root / "bench/traffic/q8.b64.json").read_text())
    traffic.update(benchkit.SMOKE_TRAFFIC, batch_per_worker=4)
    (root / "bench/traffic/q8.b4.json").write_text(json.dumps(traffic))
    limits = json.loads(
        (root / "bench/limits/dcgan32.q8.b64.json").read_text())
    (root / "bench/limits/tiny.q8.b4.json").write_text(json.dumps(limits))
    manifest["configs"].append({"name": "tiny", "source": "test",
                                "file": "bench/configs/tiny.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "tiny.q8.b4", "config": "tiny",
                                  "traffic": "q8.b4", "chips": 1,
                                  "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    spec = run.resolve(str(root), "tiny.q8.b4")
    assert spec["config"]["gan_config"]["base_width"] == 8
    out = run.run_cell(spec, benchkit.SEED, 0.2, False,
                       run.device_info())
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"samples_per_s", "step_ms_p95",
                                   "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == jax.devices()[0].platform
