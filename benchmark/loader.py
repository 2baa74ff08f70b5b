"""How a benchmark configuration reaches the program's normal serving path.

``cli/infer.load_model`` knows ``tiny-random``, ``eventgpt-7b-random`` and
HF checkpoint directories. A benchmark PR may not edit it, so this module
replaces that one name with a function that also understands
``--model_path bench:<config name>``: the config file's HF-style dict goes
through the program's own ``config.from_hf_config``, the seeded tree comes
from ``benchmark/weights.py`` at the shapes the program serves, and the
tokenizer is the program's byte tokenizer with one change (``id_tokenizer``):
it renders every id as one character of its own, so that a streamed answer
carries its token ids. Every other spelling passes through untouched. Everything below the loader is the program's own:
``prepare_model``, ``ContinuousBatcher``, ``ServingEngine``,
``make_handler``, built by ``cli.serve.build_parser`` / ``build_server``.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PREFIX = "bench:"
# A served id is rendered as the character ID_BASE + id: the two
# supplementary private-use planes hold every id of a 92544-row head.
ID_BASE = 0xF0000


def ids_of(text: str) -> list:
    """The token ids a streamed answer's text stands for."""
    return [ord(c) - ID_BASE for c in text]


def id_tokenizer():
    """The program's byte tokenizer, encoding as it does; decoding renders
    every id, special or not, as one character of its own. The byte
    tokenizer renders almost no id of a real head (bytes are 3..258 of
    32000 and more), so its streamed deltas are empty; a real tokenizer
    renders every id, and so does this one, reversibly: the client counts
    and checks the tokens of every streamed answer without asking the
    program for them."""
    from eventgpt_tpu.data.tokenizer import ByteTokenizer

    class IdTokenizer(ByteTokenizer):
        def decode(self, ids, skip_special_tokens: bool = True) -> str:
            return "".join(chr(ID_BASE + int(i)) for i in ids)

        def batch_decode(self, batch, skip_special_tokens: bool = True):
            return [self.decode(ids) for ids in batch]

    return IdTokenizer()


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def read_benchmark() -> dict:
    return read_json(os.path.join(ROOT, "BENCHMARK.json"))


def config_file(name: str, bench: dict | None = None) -> str:
    """The file of a configuration, by its name in BENCHMARK.json; a name
    that is not listed there resolves to ``benchmark/configs/<name>.json``
    (the rehearsal's toy widths)."""
    bench = bench if bench is not None else read_benchmark()
    for c in bench["configs"]:
        if c["name"] == name:
            return os.path.join(ROOT, c["file"])
    path = os.path.join(HERE, "configs", name + ".json")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"configuration {name!r}: not in BENCHMARK.json and no {path}")
    return path


class Seam:
    """What the replaced ``load_model`` built, kept for the reference: the
    program's config object and the benchmark-made device tree."""

    def __init__(self, seed: int, rehearsal: bool):
        self.seed = int(seed)
        self.rehearsal = rehearsal
        self.cfg = None
        self.tree = None
        self.hf = None
        self._original = None

    def install(self) -> None:
        from eventgpt_tpu.cli import infer

        self._original = infer.load_model
        seam = self

        def load_model(model_path, dtype, attn_impl=None, tokenizer_path=None,
                       quant="none", fuse=False):
            if not str(model_path).startswith(PREFIX):
                return seam._original(model_path, dtype, attn_impl,
                                      tokenizer_path, quant=quant, fuse=fuse)
            import jax.numpy as jnp

            from eventgpt_tpu.config import from_hf_config
            from eventgpt_tpu.models.synthetic import served_shapes

            from benchmark import weights

            hf = read_json(config_file(model_path[len(PREFIX):]))
            # The chip runs the Pallas flash prefill; the rehearsal's CPU
            # would only interpret it.
            cfg = from_hf_config(
                hf, attn_impl="dense" if seam.rehearsal else "flash")
            jdt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
            tokenizer = id_tokenizer()
            # No answer ends before its budget: the end-of-sequence id's
            # column of the head is zero (benchmark/weights.py).
            tree = weights.make_tree(served_shapes(cfg, jdt, quant, fuse),
                                     seam.seed,
                                     never=(tokenizer.eos_token_id,))
            seam.cfg, seam.tree, seam.hf = cfg, tree, hf
            return cfg, tree, tokenizer

        infer.load_model = load_model

    def uninstall(self) -> None:
        if self._original is not None:
            from eventgpt_tpu.cli import infer

            infer.load_model = self._original
            self._original = None


def server_argv(config_name: str, hf: dict, cell: dict, rehearsal: bool,
                profile_dir: str | None = None) -> list:
    """The CLI's argument list for one cell: the configuration's flags, then
    the cell's own (a four-chip cell names its mesh), loopback on an
    ephemeral port."""
    flags = list(hf["flags"]) + list(cell.get("flags", []))
    argv = ["--model_path", PREFIX + config_name] + flags + [
        "--host", "127.0.0.1", "--port", "0"]
    if profile_dir:
        argv += ["--profile_dir", profile_dir]
    return argv
