"""The command line as it stands: what a parser refuses, what the run
scripts pass, that every script left in ``scripts/`` still starts, and how
many flags the server has."""

import ast
import functools
import importlib.util
import os
import re
import runpy
import subprocess
import sys

import pytest

from eventgpt_tpu.cli import eval as eval_cli
from eventgpt_tpu.cli import export as export_cli
from eventgpt_tpu.cli import infer as infer_cli
from eventgpt_tpu.cli import serve as serve_cli
from eventgpt_tpu.cli import train as train_cli

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLIS = {"serve": serve_cli, "infer": infer_cli, "eval": eval_cli,
        "export": export_cli, "train": train_cli}
FLAG = re.compile(r"(?<![\w-])--[a-z][a-z0-9_]*")


@functools.lru_cache(maxsize=None)
def flags_of(cli: str) -> frozenset:
    """Every option string ``python -m eventgpt_tpu.cli.<cli>`` takes."""
    return frozenset(s for a in CLIS[cli].build_parser()._actions
                     for s in a.option_strings)


# What each parser insists on, so that a case reaches ``--quant``.
_REQUIRED = {"serve": [],
             "infer": ["--model_path", "m", "--query", "q",
                       "--event_frame", "e.npy"],
             "eval": ["--model_path", "m", "--event_frames", "e.npy"]}


@pytest.mark.parametrize("cli", sorted(_REQUIRED))
def test_quant_choices(cli, capsys):
    parser = CLIS[cli].build_parser()
    assert parser.parse_args(_REQUIRED[cli] + ["--quant", "int8"]).quant \
        == "int8"
    with pytest.raises(SystemExit) as e:
        parser.parse_args(_REQUIRED[cli] + ["--quant", "int4"])
    assert e.value.code == 2
    assert "invalid choice: 'int4'" in capsys.readouterr().err


@pytest.mark.parametrize("script", ["eventgpt_infer.sh", "eventgpt_eval.sh",
                                    "eventgpt_export.sh",
                                    "eventgpt_train.sh"])
def test_run_script_flags_exist(script):
    with open(os.path.join(ROOT, "script", script)) as f:
        text = f.read()
    (cli,) = set(re.findall(r"python -m eventgpt_tpu\.cli\.(\w+)", text))
    passed = set(FLAG.findall(text))
    assert passed and passed <= flags_of(cli), passed - flags_of(cli)


# The Python scripts left in scripts/: with a parser, or reading sys.argv
# by hand under their ``__main__`` check.
_WITH_PARSER = ["egpt_check", "medusa_acceptance", "serve_demo",
                "spec_acceptance_sim", "stream_demo", "trace_scopes",
                "train_medusa"]
_BY_HAND = ["expert_flips", "lint_telemetry"]


def test_every_script_is_listed():
    have = {f[:-3] for f in os.listdir(os.path.join(ROOT, "scripts"))
            if f.endswith(".py")}
    assert have == set(_WITH_PARSER + _BY_HAND)


def _imports_resolve(path: str) -> None:
    """Every import statement of the file, those inside functions too,
    names a module that can be found, and what it takes from this
    package is there."""
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                assert importlib.util.find_spec(alias.name), alias.name
        elif isinstance(node, ast.ImportFrom) and not node.level:
            assert importlib.util.find_spec(node.module), node.module
            if node.module.split(".")[0] == "eventgpt_tpu":
                module = importlib.import_module(node.module)
                for alias in node.names:
                    assert hasattr(module, alias.name) or \
                        importlib.util.find_spec(
                            f"{node.module}.{alias.name}"), \
                        (node.module, alias.name)


@pytest.mark.parametrize("name", _WITH_PARSER + _BY_HAND)
def test_script_starts(name):
    """No script imports what is gone, and each still starts: ``--help``
    runs the top of the file and the parser's construction; a script
    without a parser is loaded short of its ``__main__`` block."""
    path = os.path.join(ROOT, "scripts", name + ".py")
    _imports_resolve(path)
    if name in _BY_HAND:
        assert callable(runpy.run_path(path, run_name="not_main")["main"])
        return
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    r = subprocess.run([sys.executable, path, "--help"], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "usage:" in r.stdout


def test_server_flag_count():
    """81 flags and ``--help``. A PR that adds or removes one says so
    here (ROADMAP D5 counts them down)."""
    assert len(serve_cli.build_parser()._actions) == 82
