"""Launch-layer units: mesh construction, sharding rules, roofline
parsing, dry-run matrix; plus a subprocess multi-device lower+compile."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch import roofline as rl


def test_collective_bytes_parser():
    hlo = textwrap.dedent("""\
        %ag = f32[8,512,192]{1,0,2} all-gather(%x), channel_id=1, replica_groups=[4,4]<=[16], dimensions={2}
        %ar = f32[8,512,576]{2,1,0} all-reduce(%y), channel_id=4, replica_groups=[4,4]<=[16], to_apply=%add
        %cp = bf16[128,64]{1,0} collective-permute(%z), channel_id=9, source_target_pairs={{0,1}}
        %rs = f32[16,16]{1,0} reduce-scatter(%w), channel_id=5, replica_groups={{0,1,2,3}}, dimensions={0}
    """)
    out = rl.collective_bytes(hlo)
    ag = 8 * 512 * 192 * 4
    assert out["all-gather"] == ag * 3 // 4
    ar = 8 * 512 * 576 * 4
    assert out["all-reduce"] == 2 * ar * 3 // 4
    assert out["collective-permute"] == 128 * 64 * 2
    rs = 16 * 16 * 4
    assert out["reduce-scatter"] == rs * 3


def test_collective_parser_skips_done_ops():
    hlo = ("%s = f32[64]{0} all-gather-start(%x), replica_groups=[2,2]<=[4]\n"
           "%d = f32[64]{0} all-gather-done(%s)\n")
    out = rl.collective_bytes(hlo)
    assert out["all-gather"] == 64 * 4 // 2  # only the -start counted


def test_roofline_terms_pick_dominant():
    v5e = "TPU v5 lite"
    t = rl.roofline_terms(flops=197e12, bytes_accessed=819e9 / 2,
                          coll_bytes=0, device_kind=v5e)
    assert t["bottleneck"] == "compute"
    assert abs(t["compute_s"] - 1.0) < 1e-9
    t2 = rl.roofline_terms(flops=1e12, bytes_accessed=819e9 * 2,
                           coll_bytes=0, device_kind=v5e)
    assert t2["bottleneck"] == "memory"


def test_roofline_unknown_device_kind_raises():
    """Peaks come from the table or not at all: a chip without
    published peaks must not be priced with another chip's."""
    with pytest.raises(KeyError, match="no published peaks"):
        rl.roofline_terms(flops=1.0, bytes_accessed=1.0, coll_bytes=0,
                          device_kind="cpu")
    from repro.launch.mesh import TARGET_DEVICE_KIND

    assert rl.peaks(TARGET_DEVICE_KIND)["flops"] == 197e12


def test_compile_cache_respects_the_environment(monkeypatch):
    """An entry point's compile cache goes where
    JAX_COMPILATION_CACHE_DIR says, with nothing set in code; without
    it, to the checkout's fixed .jax_cache directory."""
    from repro.common import compile_cache as cc

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(cc.CACHE_ENV, "/elsewhere/cache")
        assert cc.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(cc.CACHE_ENV)
        path = cc.enable_compile_cache()
        assert path == str(cc.CHECKOUT_CACHE)
        assert cc.CHECKOUT_CACHE.parent.joinpath("chip_smoke.py").exists()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_model_flops_moe_counts_active_only():
    from repro.configs import get_config
    from repro.models.config import TRAIN_4K

    dense = rl.active_params(get_config("olmo-1b"))
    assert 1.0e9 < dense < 1.6e9  # ~1.2B incl. embeddings
    moe_active = rl.active_params(get_config("deepseek-v2-lite-16b"))
    assert moe_active < 4.0e9  # ~2.7B active of ~16B total


def test_cell_matrix_covers_assignment():
    from repro.launch.dryrun import cell_matrix

    cells = cell_matrix()
    assert len(cells) == 40  # 10 archs x 4 shapes
    skipped = [(a, s) for a, s, active in cells if not active]
    # long_500k skipped for the 8 non-sub-quadratic archs
    assert len(skipped) == 8
    assert all(s == "long_500k" for _, s in skipped)
    assert not any(a in ("recurrentgemma-9b", "xlstm-1.3b")
                   for a, _ in skipped)


def test_make_production_mesh_requires_devices():
    from repro.launch.mesh import make_production_mesh

    # this test process has 1 device -> must raise with guidance
    with pytest.raises(RuntimeError, match="force_host_platform"):
        make_production_mesh()


@pytest.mark.slow
def test_multi_device_lower_compile_subprocess():
    """Spawn a fresh process with 16 virtual devices and lower+compile a
    scaled arch on a 4x4 mesh — the dry-run path end to end."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
        import jax
        from repro.configs import get_config
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.steps import lowerable
        from repro.models.config import ShapeConfig
        from repro.models.model_zoo import build_model

        cfg = get_config("smollm-135m")
        model = build_model(cfg)
        mesh = make_debug_mesh(4, 4)
        shape = ShapeConfig("t", 512, 32, "train")
        fn, shardings, args = lowerable(model, shape, mesh)
        with mesh:
            compiled = jax.jit(fn, in_shardings=shardings).lower(
                *args).compile()
        ca = compiled.cost_analysis()
        assert ca.get("flops", 0) > 0
        print("OK", int(ca["flops"]))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", code], cwd=
                          os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))),
                          env=env, capture_output=True, text=True,
                          timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


def test_dryrun_artifacts_if_present():
    """If the sweep artifacts exist, every runnable cell must be ok and
    every cell file present (40 x 2 meshes)."""
    art = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "artifacts", "dryrun")
    if not os.path.isdir(art):
        pytest.skip("dry-run artifacts not generated")
    files = [f for f in os.listdir(art) if f.endswith(".json")]
    matrix = [f for f in files if not f.startswith("perona-fingerprint")]
    if len(matrix) < 80:
        pytest.skip("sweep incomplete")
    assert len(matrix) == 80  # 10 archs x 4 shapes x 2 meshes
    for f in files:
        rec = json.load(open(os.path.join(art, f)))
        assert rec["status"] in ("ok", "skipped"), (f, rec.get("error"))
