"""Jit-hygiene lint (ISSUE 8 tentpole, rule ``jit-cache``).

Executable management is a convention in this repo, learned the hard
way (DISTRIBUTED.md):

  * configuration is DECLARED at the jit site — ``static_argnames`` /
    ``static_argnums`` / ``donate_argnums`` / ``donate_argnames`` /
    ``out_shardings`` / ``in_shardings`` — because an undeclared donate
    silently doubles resident HBM and an unpinned out-sharding breaks
    donated-cache aliasing (a second full-size cache per segment, the
    ``_get_sharded_prefill`` reasoning); explicit empty pins
    (``static_argnames=()``) count — they say the author considered
    them;
  * executables for shape-bucketed callables land in a CACHE keyed by
    the bucket — the ``@functools.lru_cache`` ``_get_sharded_*`` getter
    pattern — never rebuilt per call: ``jax.jit(f)`` constructed inside
    a plain function re-traces and re-compiles on EVERY invocation.

This rule scans every ``jax.jit`` / ``pjit`` site in ``eventgpt_tpu/``
(direct calls, ``functools.partial(jax.jit, ...)`` applications, and
bare ``@jax.jit`` decorators) and flags:

  * **bare jit** — a site declaring none of the config kwargs, unless
    it lives inside an lru_cache'd getter (there the closure IS the
    config, resolved once per cache key);
  * **untracked executable creation** — a non-decorator ``jax.jit(...)``
    call inside a plain (un-cached) function: re-trace + re-compile per
    call, the exact failure mode the ``_get_sharded_*`` pattern exists
    to make impossible;
  * **jit in a loop** — the same inside ``for``/``while``: a recompile
    per iteration, the worst case.

The factory form — ``@functools.partial(jax.jit, ...)`` decorating a
nested ``def`` inside a ``make_*`` builder (train steps) — is allowed
when configured: the executable's lifetime is the returned closure's,
built once per trainer.
"""

from __future__ import annotations

import ast
from typing import List, Tuple

from eventgpt_tpu.analysis.core import Context, Finding, Rule

_CONFIG_KWARGS = ("static_argnums", "static_argnames", "donate_argnums",
                  "donate_argnames", "out_shardings", "in_shardings",
                  "device", "backend")
_CACHE_DECOS = ("lru_cache", "cache")


def _is_jit_ref(node: ast.AST) -> bool:
    """``jax.jit`` / ``pjit`` referenced (not called) — attribute or
    bare name."""
    if isinstance(node, ast.Attribute):
        return node.attr in ("jit", "pjit")
    if isinstance(node, ast.Name):
        return node.id == "pjit"
    return False


def _partial_of_jit(call: ast.Call) -> bool:
    """``functools.partial(jax.jit, **cfg)`` — the decorator idiom."""
    fn = call.func
    is_partial = (isinstance(fn, ast.Attribute) and fn.attr == "partial") \
        or (isinstance(fn, ast.Name) and fn.id == "partial")
    return bool(is_partial and call.args and _is_jit_ref(call.args[0]))


def _config_kwargs(call: ast.Call) -> List[str]:
    return [kw.arg for kw in call.keywords if kw.arg in _CONFIG_KWARGS]


def _has_cache_deco(fn: ast.AST) -> bool:
    for deco in getattr(fn, "decorator_list", ()):
        target = deco.func if isinstance(deco, ast.Call) else deco
        name = (target.attr if isinstance(target, ast.Attribute)
                else target.id if isinstance(target, ast.Name) else "")
        if name in _CACHE_DECOS:
            return True
    return False


class JitHygieneRule(Rule):
    id = "jit-cache"
    doc = ("every jax.jit/pjit site declares its static/donate/sharding "
           "config and lands its executable at module scope or in an "
           "lru_cache'd getter (_get_sharded_* pattern); no per-call or "
           "in-loop executable creation")

    def run(self, ctx: Context) -> List[Finding]:
        findings: List[Finding] = []
        for s in ctx.sources:
            if s.tree is None or not s.rel.startswith("eventgpt_tpu/"):
                continue
            parents = s.parents()
            for node in ast.walk(s.tree):
                if isinstance(node, ast.Call) and _is_jit_ref(node.func):
                    # jax.jit(f, **cfg) — direct executable creation.
                    self._check(s, node, _config_kwargs(node), parents,
                                findings, call_form=True)
                elif isinstance(node, ast.Call) and _partial_of_jit(node):
                    # functools.partial(jax.jit, **cfg) — decorator /
                    # module-application idiom; the partial itself is
                    # config declaration, its application creates the
                    # executable wherever it happens.
                    self._check(s, node, _config_kwargs(node), parents,
                                findings, call_form=False)
                elif isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    for deco in node.decorator_list:
                        if _is_jit_ref(deco):
                            # bare @jax.jit decorator: no Call node
                            # exists, so it needs its own branch.
                            self._check(s, deco, [], parents, findings,
                                        call_form=False,
                                        decorated=node)
        return findings

    def _context(self, node: ast.AST, parents,
                 decorated=None) -> Tuple[list, bool, bool]:
        """(enclosing function chain, in_loop, is_decorator)."""
        chain: list = []
        in_loop = False
        is_deco = decorated is not None
        cur = decorated if decorated is not None else node
        while True:
            p = parents.get(cur)
            if p is None:
                break
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if cur in p.decorator_list:
                    is_deco = True
                else:
                    chain.append(p)
            if isinstance(p, (ast.For, ast.While)):
                in_loop = True
            cur = p
        return chain, in_loop, is_deco

    def _check(self, s, node: ast.AST, cfg: List[str], parents,
               findings: List[Finding], call_form: bool,
               decorated=None) -> None:
        chain, in_loop, is_deco = self._context(node, parents, decorated)
        cached = any(_has_cache_deco(fn) for fn in chain)
        if not cfg and not cached:
            where = ("module scope" if not chain
                     else f"'{chain[0].name}'")
            findings.append(Finding(
                self.id, s.rel, node.lineno,
                f"bare jax.jit at {where}: none of "
                f"static_argnums/static_argnames/donate/out_shardings "
                f"declared",
                hint="declare the pins (explicit empty tuples count) "
                     "or move the site into an lru_cache'd getter"))
        if not chain:
            return  # module scope: one executable for the process life
        if cached or is_deco:
            return  # bucket-keyed getter / factory closure: tracked
        if in_loop:
            findings.append(Finding(
                self.id, s.rel, node.lineno,
                "jax.jit executable created inside a loop — retrace + "
                "recompile per iteration",
                hint="hoist into an lru_cache'd _get_* getter keyed by "
                     "the shape bucket"))
        elif call_form:
            findings.append(Finding(
                self.id, s.rel, node.lineno,
                "untracked executable creation: jax.jit(...) inside a "
                "plain function re-traces and re-compiles per call",
                hint="land it in an lru_cache'd getter (the "
                     "_get_sharded_* pattern) or at module scope"))
