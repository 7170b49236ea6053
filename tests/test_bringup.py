"""The bring-up rules (ISSUE 22), fast and CPU-only: where JAX may run,
where compiled programs are kept, which devices a mesh takes, and
chip_smoke.py's control flow end to end on the CPU at tiny-llama."""

import json
import os
import subprocess
import sys

import jax
import pytest

from ggrmcp_tpu.core import config as cfgmod
from ggrmcp_tpu.core.config import MeshConfig
from ggrmcp_tpu.parallel import mesh as mesh_mod
from ggrmcp_tpu.utils import jaxenv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestPlatformRule:
    def test_refuses_a_cpu_nobody_asked_for(self, monkeypatch):
        monkeypatch.delenv("JAX_PLATFORMS")
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            jaxenv.require_accelerator("sidecar")

    def test_accepts_the_cpu_when_asked(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        jaxenv.require_accelerator("sidecar")  # no raise

    def test_fleet_with_colaunch_is_refused_off_the_cpu(self, monkeypatch):
        cfg = cfgmod.default()
        cfg.fleet.enabled = True
        cfg.validate(colaunch=True)  # JAX_PLATFORMS=cpu: CPU replicas
        monkeypatch.delenv("JAX_PLATFORMS")
        cfg.validate()  # fleet without --tpu stays legal
        with pytest.raises(ValueError, match="one per host"):
            cfg.validate(colaunch=True)


class TestCompileCache:
    @pytest.fixture
    def updates(self, monkeypatch):
        calls = []
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: calls.append((k, v))
        )
        return calls

    def test_env_set_means_nothing_is_set_in_code(self, monkeypatch, updates):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert jaxenv.configure_compile_cache() == "/somewhere/else"
        assert updates == []

    def test_unset_means_the_checkout(self, monkeypatch, updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO, ".jax_cache")
        assert jaxenv.configure_compile_cache() == want
        assert updates == [("jax_compilation_cache_dir", want)]


class TestMeshSubset:
    def test_fixed_axes_take_the_first_devices(self):
        """How a four-chip host is asked for one chip: every axis
        fixed, product below the device count."""
        devs = jax.devices()
        assert len(devs) == 8
        one = mesh_mod.build_mesh(MeshConfig(tensor=1))
        assert [d.id for d in one.devices.flat] == [devs[0].id]
        four = mesh_mod.build_mesh(MeshConfig(tensor=4))
        assert [d.id for d in four.devices.flat] == [d.id for d in devs[:4]]
        assert mesh_mod.mesh_shape_str(four) == "tensor=4"

    def test_an_inferred_axis_still_takes_every_device(self):
        assert mesh_mod.build_mesh(MeshConfig()).devices.size == 8
        with pytest.raises(ValueError, match="not divisible"):
            mesh_mod.build_mesh(MeshConfig(tensor=3, data=0))


class TestChipSmoke:
    def _run(self, *args, env=None):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
        )

    def test_without_a_tpu_it_fails_and_prints_no_result(self):
        env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        proc = self._run(env=env)
        assert proc.returncode != 0
        assert "refuses a non-TPU backend" in proc.stdout
        last = proc.stdout.strip().splitlines()[-1]
        assert last.startswith("CHIP SMOKE FAILED")
        assert '"ok"' not in proc.stdout

    def test_cpu_rehearsal_end_to_end(self):
        """Every leg's control flow — kernel check (interpreted),
        gateway --tpu with paged KV and without, both request passes,
        every assertion read from the running stack — at tiny-llama."""
        proc = self._run("--cpu-rehearsal")
        assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
        lines = proc.stdout.strip().splitlines()
        assert "NOT a chip result" in lines[0]
        result = json.loads(lines[-1])
        assert result["ok"] is True and result["rehearsal"] is True
        assert result["device"]["platform"] == "cpu"
        assert "all legs passed: kernel,sparse,keye,jamba,serve,default_kv" in proc.stdout
        # The sparse leg held one expert layer of the tiny deepseek_v32
        # member, a sparse chunk and a sparse decode step, to the
        # float32 reference layer, and saw the selection bind.
        assert "sparse chunk (16 of 65..80 keys)" in proc.stdout
        assert "sparse decode step (16 of 81..81 keys)" in proc.stdout
        # The keye leg the same for one layer of the tiny keye member,
        # and both of its sparse paths ran.
        assert "keye leg ok" in proc.stdout
        # The jamba leg: the whole tiny hybrid model, a chunk through the
        # scan and decode steps through the pool, to float32 logits.
        assert "jamba leg ok" in proc.stdout
        assert "ssm_scan 1, ssm_step 1" in proc.stdout
        assert "sparse_gqa_chunk 1, sparse_gqa_decode 1" in proc.stdout
        assert proc.stdout.count("sparse chunk (16 of 65..80 keys)") == 3
        assert "paged vs contiguous: first 8 of 8 ids agree" in proc.stdout
        # The kernel leg walked both kernels, the paged-decode one with
        # and without a window that binds.
        assert proc.stdout.count("paged decode q[4,1,8,32]") == 2
        assert "paged_decode_attention window=12" in proc.stdout
