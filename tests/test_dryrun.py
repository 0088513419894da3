"""Dry-run machinery on a small forced-device mesh (subprocess) + HLO
collective-census parser unit tests."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.dryrun_lib import collective_census, _shape_bytes


def test_shape_bytes():
    assert _shape_bytes("bf16[2,512,128]") == 2 * 512 * 128 * 2
    assert _shape_bytes("f32[16]") == 64
    assert _shape_bytes("(f32[8], u8[4,4])") == 32 + 16
    assert _shape_bytes("pred[]") == 1


def test_collective_census_parses_kinds():
    hlo = """
      %ag = bf16[2,1024]{1,0} all-gather(%x), replica_groups={}
      %ar = f32[512]{0} all-reduce(%y), to_apply=%sum
      %rs.1 = f32[64]{0} reduce-scatter(%z), dimensions={0}
      %a2a = (f32[8,8]{1,0}, f32[8,8]{1,0}) all-to-all(%p, %q)
      %cp = u8[128]{0} collective-permute(%w), source_target_pairs={{0,1}}
      %ags = bf16[4,256]{1,0} all-gather-start(%v), replica_groups={}
    """
    c = collective_census(hlo)
    assert c["all-gather"]["count"] == 2
    assert c["all-reduce"]["count"] == 1
    assert c["all-reduce"]["bytes"] == 2 * 512 * 4  # 2x factor
    assert c["reduce-scatter"]["count"] == 1
    assert c["all-to-all"]["bytes"] == 2 * 64 * 4
    assert c["collective-permute"]["count"] == 1
    assert c["total_bytes"] > 0


def test_chip_peaks_known_kind_and_unknown_kind_raises():
    from repro.launch.chips import DRYRUN_TARGET, chip_peaks

    v5e = chip_peaks("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bw) == (197e12, 819e9)
    assert chip_peaks(DRYRUN_TARGET) is v5e
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("cpu")


def test_compile_cache_follows_env_else_fixed_checkout_dir(monkeypatch):
    from pathlib import Path

    from repro.launch import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    root = Path(__file__).resolve().parents[1]
    assert compile_cache.DEFAULT_DIR == root / ".jax_cache"
    ignored = (root / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    from repro.launch import dryrun_lib as lib
    from repro.train.train_step import StepConfig
    from repro.configs.base import ShapeConfig

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    shapes = [ShapeConfig("train_4k", 256, 8, "train"),
              ShapeConfig("decode_32k", 512, 8, "decode")]
    for so in shapes:
        rec = lib.run_cell("tinyllama-1.1b", so.name, mesh, "/tmp/dry_test",
                           "t", StepConfig(), shape_override=so)
        assert rec["status"] == "ok", rec
        assert rec["memory"]["peak_per_device"] > 0
        assert rec["cost"].get("flops", 0) > 0
        assert rec["collectives"]["total_bytes"] > 0
    # skip rule
    import pytest
    rec = lib.run_cell("yi-9b", "long_500k", mesh, "/tmp/dry_test", "t",
                       StepConfig())
    assert rec["status"] == "skip"
    print("OK")
""")


@pytest.mark.slow
def test_dryrun_small_mesh_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, timeout=560)
    assert "OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
