"""Trace-safety linter: synthetic anti-pattern fixtures, suppression
syntax, traced-context discovery, and the clean-tree gate over the real
hot-path modules."""
import os
import textwrap

import repro
from repro.analysis.tracecheck import (JNP_ALLOWLIST, ContextIndex,
                                       lint_paths, load_modules)

# repro is a namespace package: locate it via __path__, not __file__
REPRO_DIR = os.path.abspath(list(repro.__path__)[0])
SRC_ROOT = os.path.dirname(REPRO_DIR)


def _write_pkg(tmp_path, source, name="pkg"):
    d = tmp_path / name
    d.mkdir()
    (d / "__init__.py").write_text("")
    (d / "mod.py").write_text(textwrap.dedent(source))
    return str(d)


BAD = """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from functools import partial


    def body(carry, x):
        if carry > 0:                      # TS101
            carry = carry - 1
        n = int(x)                         # TS102
        v = x.item()                       # TS102
        h = np.tanh(carry)                 # TS103
        while x > 0:                       # TS101
            x = x - 1
        ok = 0 if x is None else 1         # exempt: identity test
        m = len(carry)                     # exempt producer
        return carry, (n, v, h, ok, m)


    def run(init, xs):
        return jax.lax.scan(body, init, xs)
"""


def test_rules_fire_on_synthetic_scan_body(tmp_path):
    rep = lint_paths([_write_pkg(tmp_path, BAD)])
    fired = rep.rules_fired()
    assert fired.get("TS101") == 2
    assert fired.get("TS102") == 2
    assert fired.get("TS103") == 1
    assert fired.get("TS105") == 1          # pkg.mod is not allowlisted
    assert not rep.ok()


def test_unreferenced_function_is_not_a_traced_context(tmp_path):
    # the same anti-patterns in a function nothing scans/jits: no finding
    src = textwrap.dedent(BAD).split("def run")[0]
    rep = lint_paths([_write_pkg(tmp_path, src)])
    assert rep.rules_fired().get("TS101") is None


def test_local_partial_alias_marks_scan_body(tmp_path):
    src = """
        import jax
        from functools import partial


        def cycle(carry, x, cfg):
            if carry > 0:                  # TS101 via alias resolution
                pass
            return carry, x


        def run(init, xs, cfg):
            body = partial(cycle, cfg=cfg)
            return jax.lax.scan(body, init, xs)
    """
    rep = lint_paths([_write_pkg(tmp_path, src)])
    assert rep.rules_fired().get("TS101") == 1


def test_transitive_callee_is_traced(tmp_path):
    src = """
        import jax
        import jax.numpy as jnp


        def helper(q):
            v = jnp.sum(q)
            n = int(v)                     # TS102, reached through body
            return n


        def body(carry, x):
            return carry, helper(carry)


        def run(init, xs):
            return jax.lax.scan(body, init, xs)
    """
    rep = lint_paths([_write_pkg(tmp_path, src)])
    assert rep.rules_fired().get("TS102") == 1


def test_suppression_comment_and_skip_file(tmp_path):
    src = """
        import jax


        def body(carry, x):
            if carry > 0:  # lint: ignore[ts101]
                pass
            n = int(carry)                 # still flagged
            return carry, n


        def run(init, xs):
            return jax.lax.scan(body, init, xs)
    """
    rep = lint_paths([_write_pkg(tmp_path, src)])
    fired = rep.rules_fired()
    assert fired.get("TS101") is None       # suppressed
    assert fired.get("TS102") == 1          # suppression is per-rule

    skip = "# lint: skip-file\n" + textwrap.dedent(src)
    d = tmp_path / "pkg2"
    d.mkdir()
    (d / "__init__.py").write_text("")
    (d / "mod.py").write_text(skip)
    rep2 = lint_paths([str(d)])
    assert not rep2.findings


def test_cache_keyed_mutable_capture(tmp_path):
    src = """
        _KNOBS = [1, 2, 3]


        def make(sim):
            return sim.run(extra_predicates=(
                lambda cspec, ctx: _KNOBS,))
    """
    rep = lint_paths([_write_pkg(tmp_path, src)])
    assert rep.rules_fired().get("TS104") == 1


def test_engine_scan_body_is_discovered():
    mods = load_modules([REPRO_DIR], root=SRC_ROOT)
    idx = ContextIndex(mods)
    ctxs = {f"{m}:{q}" for (m, q) in idx.contexts}
    # the partial(cycle, ...) -> _scan_cycles -> lax.scan chain resolves
    assert "repro.core.engine:make_run.cycle" in ctxs
    # and the hot-path callees are transitively traced
    for want in ("repro.core.controller:controller_step",
                 "repro.core.device:issue",
                 "repro.core.frontend:system_frontend_insert"):
        assert want in ctxs, want
    # the scan body's params count as traced values
    key = ("repro.core.engine", "make_run.cycle")
    assert idx.contexts[key] is True


def test_hot_path_modules_lint_clean():
    paths = [os.path.join(REPRO_DIR, "core", f"{m}.py")
             for m in ("engine", "controller", "frontend", "device")]
    # lint the whole package so cross-module contexts resolve, then gate
    # on the hot-path files specifically
    rep = lint_paths([REPRO_DIR], root=SRC_ROOT)
    hot = [f for f in rep.findings if f.path in paths]
    assert not hot, [f.render() for f in hot]
    # and the whole tree is clean too (TS105 allowlist up to date)
    assert rep.ok(strict=True), rep.summary()


def test_allowlist_names_only_real_modules():
    mods = load_modules([REPRO_DIR], root=SRC_ROOT)
    missing = [m for m in JNP_ALLOWLIST if m not in mods]
    assert not missing, missing
