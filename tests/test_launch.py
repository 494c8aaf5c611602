"""Launch-layer tests: collective-traffic parser, analytic attention flops,
mesh construction, and the fault-tolerant train launcher (kill/resume)."""
import os
import subprocess
import sys

import pytest
from _hypothesis_compat import given, settings, st

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCollectiveParser:
    def _parse(self, hlo):
        import repro.launch.dryrun as dr

        return dr.collective_bytes_from_hlo(hlo)

    def test_all_reduce_ring_accounting(self):
        hlo = (
            "%all-reduce.1 = f32[1024]{0} all-reduce(%x), "
            "replica_groups={{0,1,2,3}}, to_apply=%add\n"
        )
        out = self._parse(hlo)
        # 2 * S * (G-1)/G = 2 * 4096 * 3/4
        assert out["all-reduce"] == pytest.approx(2 * 4096 * 3 / 4)

    def test_iota_replica_groups_v2(self):
        hlo = (
            "%all-gather.3 = bf16[2048,512]{1,0} all-gather(%p), "
            "channel_id=7, replica_groups=[16,16]<=[16,16]T(1,0), "
            "dimensions={0}, use_global_device_ids=true\n"
        )
        out = self._parse(hlo)
        S = 2048 * 512 * 2
        assert out["all-gather"] == pytest.approx(S * 15 / 16)

    def test_tuple_shapes_and_start_ops(self):
        hlo = (
            "%ar = (f32[128]{0}, f32[256]{0}) all-reduce-start(%a, %b), "
            "replica_groups={{0,1}}\n"
            "%d = (f32[128]{0}, f32[256]{0}) all-reduce-done(%ar)\n"
        )
        out = self._parse(hlo)
        S = (128 + 256) * 4
        assert out["all-reduce"] == pytest.approx(2 * S * 0.5)

    def test_non_collectives_ignored(self):
        hlo = (
            "%dot.1 = f32[128,128]{1,0} dot(%a, %b)\n"
            "%fusion.all-reduce-like = f32[4]{0} add(%x, %y)\n"
        )
        out = self._parse(hlo)
        assert out["total"] == 0.0


class TestAnalyticAttention:
    def _brute(self, T, q_offset, window):
        total = 0
        for t in range(q_offset, q_offset + T):
            vis = t + 1
            if window is not None:
                vis = min(vis, window)
            total += vis
        return total

    @settings(max_examples=40, deadline=None)
    @given(
        T=st.integers(1, 300),
        off=st.integers(0, 200),
        w=st.one_of(st.none(), st.integers(1, 128)),
    )
    def test_visible_context_closed_form(self, T, off, w):
        from repro.launch.analysis import visible_context_sum

        assert visible_context_sum(T, off, w) == self._brute(T, off, w)

    def test_attention_flops_families(self):
        from repro.configs import ARCHS
        from repro.launch.analysis import attention_flops

        # attention-free arch: zero attention flops
        assert attention_flops(ARCHS["falcon-mamba-7b"], "train", 8, 1024) == 0
        # windowed < full for the same geometry
        full = attention_flops(ARCHS["mistral-nemo-12b"], "train", 1, 65536)
        # recurrentgemma has 1/3 attn layers AND a 2048 window
        hyb = attention_flops(ARCHS["recurrentgemma-9b"], "train", 1, 65536)
        assert hyb < full


class TestMesh:
    def test_make_production_mesh_is_a_function_not_constant(self):
        import inspect

        from repro.launch import mesh as mesh_mod

        assert callable(mesh_mod.make_production_mesh)
        src = inspect.getsource(mesh_mod)
        assert "make_mesh" in src
        # no module-level mesh: importing never touched jax device state
        assert not any(
            isinstance(v, object) and type(v).__name__ == "Mesh"
            for v in vars(mesh_mod).values()
        )


class TestTrainLauncherResume:
    def test_kill_and_resume_continues_from_committed_step(self, tmp_path):
        """Run 40 steps with checkpoints every 20; then 'restart' with a
        60-step budget — the second run must resume from step 40 and the
        loss trajectory must continue (fault-tolerance deliverable)."""
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(_ROOT, "src")
        env["JAX_PLATFORMS"] = "cpu"  # a child never takes the chip
        base = [
            sys.executable, "-m", "repro.launch.train",
            "--arch", "qwen3-1.7b", "--reduced",
            "--batch", "2", "--seq", "32", "--lr", "1e-3",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "20",
            "--resume", "auto", "--log-every", "20",
        ]
        r1 = subprocess.run(
            base + ["--steps", "40"], capture_output=True, text=True,
            env=env, timeout=560,
        )
        assert r1.returncode == 0, r1.stderr
        assert "step    40" in r1.stdout
        r2 = subprocess.run(
            base + ["--steps", "60"], capture_output=True, text=True,
            env=env, timeout=560,
        )
        assert r2.returncode == 0, r2.stderr
        assert "restored committed step 40" in r2.stdout
        # it did NOT redo steps 1..40
        assert "step    20 " not in r2.stdout
        assert "step    60" in r2.stdout
