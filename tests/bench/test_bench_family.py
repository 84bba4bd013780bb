"""A model family added as files alone runs end to end.

The MLP GAN that the program builds for vector data (`GANConfig(image_size
=0)`), with 2-D data, its own reference and its own FLOP count
(`fixtures/mlp_family.py`), goes into a checkout beside a copy of `bench/`
as `bench/families/mlp.py`, with a configuration, a traffic mix, limits and
manifest entries. A run of its cell on the CPU reads `correct`, `mfu` reads
the family's count, and every file copied from `bench/` is as it was.
"""
import filecmp
import json
import os
import shutil

import jax
import pytest

import benchkit

import run

CONFIG = {
    "name": "mlp2d",
    "family": "mlp",
    "source": "https://arxiv.org/abs/2010.13359",
    "what": "The MLP GAN of the repo's quickstart on a ring of 8 Gaussians.",
    "gan_config": {"name": "mlp2d", "image_size": 0, "data_dim": 2,
                   "latent_dim": 16, "hidden": 64, "weight_clip": 0.1,
                   "disc_grad_mult": 5.0},
    "data": {"modes": 8, "radius": 2.0, "std": 0.02},
    # weights 2x64, 64x64, 64x1 and 16x64, 64x64, 64x2; biases 129 and 130
    "n_params": 4288 + 5248 + 129 + 130,
    "dtype": "float32",
    "matmul_precision": "default",
}
CELL = "mlp2d.q8.b16"


def _checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(benchkit.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(benchkit.HERE, "fixtures", "mlp_family.py"),
                root / "bench/families/mlp.py")
    (root / "bench/configs/mlp2d.json").write_text(json.dumps(CONFIG))
    traffic = json.loads((root / "bench/traffic/q8.b64.json").read_text())
    traffic.update(batch_per_worker=16, pool_batches=4)
    (root / "bench/traffic/q8.b16.json").write_text(json.dumps(traffic))
    shutil.copy(root / "bench/limits/dcgan32.q8.b64.json",
                root / f"bench/limits/{CELL}.json")
    manifest = run.load_json(os.path.join(benchkit.REPO, "BENCHMARK.json"))
    manifest["configs"].append({"name": "mlp2d", "source": CONFIG["source"],
                                "file": "bench/configs/mlp2d.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": CELL, "config": "mlp2d",
                                  "traffic": "q8.b16", "chips": 1,
                                  "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def test_new_family_from_files_alone(tmp_path):
    from repro.models import build

    root = _checkout(tmp_path)
    spec = run.resolve(str(root), CELL)
    fam = spec["family"]
    assert os.path.samefile(fam.__file__, root / "bench/families/mlp.py")

    # the family's sizes and count are the program's model's
    model = build(fam.program_config(spec["config"]))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert [x.size for x in jax.tree.leaves(shapes)] == \
        fam.param_sizes(spec["config"])
    assert fam.n_params(spec["config"]) == CONFIG["n_params"]

    out = run.run_cell(spec, benchkit.SEED, 0.2, False, run.device_info())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"samples_per_s", "step_ms_p95",
                                   "setup_s"}

    ctx = {"family": fam, "config": spec["config"],
           "traffic": spec["traffic"], "steps": 10, "window_s": 0.5,
           "chips": 1, "peaks": {"bf16_tflops": 197.0}}
    per_step = fam.step_flops(spec["config"], 16, 1)
    assert run.reader(spec["metrics_dir"], "mfu")(ctx) == pytest.approx(
        100 * per_step * 10 / 0.5 / 197e12)

    for here, _, files in os.walk(benchkit.BENCH):
        if "__pycache__" in here:
            continue
        rel = os.path.relpath(here, benchkit.BENCH)
        for f in files:
            assert filecmp.cmp(os.path.join(here, f),
                               root / "bench" / rel / f, shallow=False), f


def test_mlp_count_against_compiled_field():
    """The MLP family's count against XLA's count of the program's field
    at the cell's batch: never above it, and short of it only by the
    elementwise work, which at width 64 is 6.5% of it."""
    import jax.numpy as jnp

    from repro.models import build

    path = os.path.join(benchkit.HERE, "fixtures", "mlp_family.py")
    fam = run.load_module(path, "bench_family_mlp_test")
    model = build(fam.program_config(CONFIG))
    params = jax.eval_shape(model.init, jax.random.key(0))
    real = jax.ShapeDtypeStruct((16, 2), jnp.float32)
    compiled = jax.jit(lambda p, r, k: model.field_fn(p, {"real": r}, k)
                       ).lower(params, real, jax.random.key(0)).compile()
    xla = compiled.cost_analysis()["flops"]
    ours = fam.step_flops(CONFIG, 16, 1)
    assert ours <= xla and (xla - ours) / xla < 0.1
