"""What the chip bring-up added: the smoke refuses a CPU, the compile
cache is placed from outside, and segment build workers never ask for
the chip.

Everything here runs on the CPU; interpreters that must start fresh are
subprocesses (a few seconds each).
"""
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env_overrides):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    for k, v in env_overrides.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return subprocess.run([sys.executable] + args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)


def test_chip_smoke_refuses_cpu():
    proc = _run(["chip_smoke.py"])
    assert proc.returncode not in (0, 2), proc.stdout + proc.stderr
    assert "platform is 'cpu'" in proc.stderr
    # no result line: nothing on stdout parses as a pass
    assert '"ok"' not in proc.stdout


def test_chip_smoke_rehearsal_reads_what_the_server_ran():
    """The CPU walk-through never passes, and its per-query dispatch
    comes from the server's span tree (EXPLAIN ANALYZE over HTTP), not
    from a copy of the routing rules: q4.1 is one segmented program,
    q2.1 — a sort-core batch over the (scaled) row limit — runs per
    segment."""
    proc = _run(["chip_smoke.py", "--rehearse-cpu"], XLA_FLAGS=None,
                PINOT_CPU_FAST_GROUPBY=None)
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert '"ok"' not in proc.stdout
    ran = dict(re.findall(r"query (\S+): plan kernel  server ran (\{.*?\})  ",
                          proc.stdout))
    assert ran["q4.1"] == "{'segmented_compact_dispatch': [1, 8, 'compact']}"
    assert ran["q2.1"] == "{'segment_kernel': [8, 8, 'compact']}"
    assert len(ran) == 5


def test_chip_smoke_mesh_rehearsal_goes_through_the_serving_node():
    """With several devices the walk-through serves the four-chip cell's
    layout (16 segments, four a device) from a ServerNode that holds the
    mesh: every query is one mesh program of the expected route, read
    from the server's span tree over HTTP, and none falls back; q4.3's
    sort core, over the (scaled) row limit on a local shard, runs per
    local segment inside the program."""
    proc = _run(["chip_smoke.py", "--rehearse-cpu"],
                XLA_FLAGS="--xla_force_host_platform_device_count=4",
                PINOT_CPU_FAST_GROUPBY=None)
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert '"ok"' not in proc.stdout
    ran = dict(re.findall(
        r"mesh query (\S+): server ran (\[.*?\])  .*?mesh_fallbacks 0  "
        r"segments 16  .*?digest_ok True", proc.stdout))
    assert ran == {
        "q1.1": "[('mesh_dispatch', 'mesh_dense', 'dense')]",
        "dgb": "[('mesh_dispatch', 'mesh_dense', 'dense')]",
        "q4.1": "[('mesh_dispatch', 'mesh_compact', 'compact')]",
        "q2.1": "[('mesh_dispatch', 'mesh_compact', 'compact')]",
        "q4.3": "[('mesh_dispatch', 'mesh_compact_per_segment', 'compact')]",
    }
    assert "16 segments, 4 a device; column shards on device ids " \
        "[0, 1, 2, 3]" in proc.stdout
    assert "multistage all_to_all join" in proc.stdout


_CACHE_PROBE = ("import pinot_tpu, jax; "
                "print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_env_is_not_overridden(tmp_path):
    outside = str(tmp_path / "cc")
    proc = _run(["-c", _CACHE_PROBE], JAX_COMPILATION_CACHE_DIR=outside)
    assert proc.stdout.strip() == outside, proc.stderr


def test_compile_cache_default_is_fixed_in_checkout():
    dirs = {_run(["-c", _CACHE_PROBE],
                 JAX_COMPILATION_CACHE_DIR=None).stdout.strip()
            for _ in range(2)}
    assert dirs == {os.path.join(REPO, ".jax_cache")}


def test_compile_cache_has_one_setter():
    setters = []
    for root, _dirs, files in os.walk(REPO):
        if any(part.startswith(".") or part == "chiprun_out"
               for part in os.path.relpath(root, REPO).split(os.sep)
               if part != "."):
            continue
        for f in files:
            if not f.endswith(".py") or f == os.path.basename(__file__):
                continue
            with open(os.path.join(root, f)) as fh:
                if re.search(r"""update\(\s*["']jax_compilation_cache_dir""",
                             fh.read()):
                    setters.append(os.path.relpath(
                        os.path.join(root, f), REPO))
    assert setters == [os.path.join("pinot_tpu", "__init__.py")]


def test_ingestion_workers_are_pinned_to_cpu(monkeypatch):
    from pinot_tpu.ingestion.batch import _worker_env
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    env = _worker_env()
    assert env["JAX_PLATFORMS"] == "cpu"
    assert REPO in env["PYTHONPATH"].split(os.pathsep)
