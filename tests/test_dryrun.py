"""Dry-run machinery in subprocesses (device-count manipulation) + the
elastic-restore path across different mesh sizes."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
       "REPRO_DRYRUN_DEVICES": "8"}


def _run(code: str, extra_env=None):
    env = dict(ENV)
    if extra_env:
        env.update(extra_env)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=600)


@pytest.mark.slow
def test_debug_mesh_cell_compiles():
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        from repro.launch.dryrun import run_cell
        res = run_cell("qwen3-0.6b", "decode_32k", multi_pod=True,
                       debug_mesh=True)
        assert res.get("ok"), res.get("error")
        assert res["collectives"], "expected collectives in partitioned HLO"
        print("OK", res["n_devices"])
    """)
    assert "OK 8" in out.stdout, out.stdout + out.stderr


@pytest.mark.slow
def test_collective_parser_counts_bytes():
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        from repro.launch.dryrun import run_cell
        res = run_cell("mistral-nemo-12b", "train_4k", multi_pod=False,
                       debug_mesh=True)
        assert res.get("ok"), res.get("error")
        wire = sum(v["wire_bytes_per_device"]
                   for v in res["collectives"].values())
        assert wire > 0, res["collectives"]
        print("WIRE_OK", int(wire))
    """)
    assert "WIRE_OK" in out.stdout, out.stdout + out.stderr


@pytest.mark.slow
def test_elastic_restore_across_device_counts(tmp_path):
    """Save on a 4-device mesh, restore+reshard on 2 devices."""
    ckpt = str(tmp_path / "elastic")
    save_code = f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.train.step import init_train_state
        from repro.ckpt.checkpoint import CheckpointManager
        cfg = dataclasses.replace(get_config("qwen3-0.6b", smoke=True),
                                  dtype="float32")
        mesh = jax.make_mesh((4,), ("data",))
        state = init_train_state(cfg, jax.random.PRNGKey(0))
        sharded = jax.device_put(
            state, NamedSharding(mesh, P()))
        mgr = CheckpointManager({ckpt!r})
        mgr.save(0, sharded, blocking=True)
        print("SAVED")
    """
    out = _run(save_code)
    assert "SAVED" in out.stdout, out.stdout + out.stderr
    restore_code = f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, dataclasses, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.train.step import init_train_state
        from repro.ckpt.checkpoint import CheckpointManager
        cfg = dataclasses.replace(get_config("qwen3-0.6b", smoke=True),
                                  dtype="float32")
        mesh = jax.make_mesh((2,), ("data",))
        like = init_train_state(cfg, jax.random.PRNGKey(1))
        shardings = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), like)
        mgr = CheckpointManager({ckpt!r})
        step, state = mgr.restore(like, shardings=shardings)
        assert step == 0, step
        ref = init_train_state(cfg, jax.random.PRNGKey(0))
        for a, b in zip(jax.tree_util.tree_leaves(state.params),
                        jax.tree_util.tree_leaves(ref.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("RESTORED_ELASTIC")
    """
    out = _run(restore_code)
    assert "RESTORED_ELASTIC" in out.stdout, out.stdout + out.stderr
