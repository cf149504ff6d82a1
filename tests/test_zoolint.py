"""zoolint — the static-analysis suite's own tests.

Five layers:

1. per-rule fixtures: each rule (six from PR 5, plus v2's
   SHARD007/MEM009/LOCK010) has at least one proven TRUE POSITIVE
   and one proven NON-FINDING;
2. interprocedural variants: JIT001/SYNC002/RNG006 findings hidden
   behind helper calls, resolved through the project layer's call
   graph;
3. framework semantics: inline suppressions (incl. the decorated-def
   either-line rule), baseline only-shrink, ``--diff`` PR gating,
   JSON schema, CLI exit codes, ``--jobs`` determinism, the
   ``--explain-comms``/``--explain-hbm`` report modes;
4. the static↔runtime parity gate: the static collective-bytes
   estimate must agree with the measured ``collective_bytes_total``
   counters of a REAL training run to within ±10%;
5. the tier-1 repo gate: the full pass over ``analytics_zoo_tpu``,
   ``scripts`` and ``examples`` must report ZERO non-baselined
   findings, and the checked-in baseline must stay strictly below
   the pre-fix finding count.

The engine is stdlib-only; importing it through the package here is
fine (tests already run with jax loaded), while ``scripts/zoolint``
exercises the jax-free file-path loading in the subprocess tests.
"""

import json
import os
import subprocess
import sys

import pytest

from analytics_zoo_tpu.analysis import (
    analyze_source, apply_baseline, diff_findings, load_baseline,
    write_baseline)
from analytics_zoo_tpu.analysis.cli import main as zoolint_main

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO_ROOT, ".zoolint-baseline.json")


def lint(src, rules=None):
    return analyze_source(src, path="snippet.py", rule_ids=rules)


def rule_ids(findings):
    return [f.rule for f in findings]


# ================================================================ JIT001


class TestJIT001:
    def test_print_and_clock_and_host_rng_in_jit(self):
        out = lint(
            "import time, random, jax\n"
            "import numpy as np\n"
            "@jax.jit\n"
            "def step(p, x):\n"
            "    print('hi')\n"
            "    t = time.time()\n"
            "    r = random.random()\n"
            "    n = np.random.normal()\n"
            "    return p * x + t + r + n\n", rules=["JIT001"])
        assert len(out) == 4
        assert all(f.rule == "JIT001" and f.severity == "error"
                   for f in out)
        assert out[0].symbol == "step"

    def test_closure_and_global_mutation_in_traced_fn(self):
        out = lint(
            "import jax\n"
            "_STATS = {}\n"
            "def make():\n"
            "    acc = []\n"
            "    def step(p, x):\n"
            "        _STATS['n'] = 1\n"
            "        acc.append(x)\n"
            "        return p\n"
            "    return jax.jit(step)\n", rules=["JIT001"])
        assert len(out) == 2
        assert "_STATS" in out[0].message
        assert ".append" in out[1].message

    def test_global_stmt_in_jitted(self):
        out = lint(
            "import jax\n"
            "N = 0\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    global N\n"
            "    N = N + 1\n"
            "    return x\n", rules=["JIT001"])
        assert any("global 'N'" in f.message for f in out)

    def test_traced_via_grad_and_scan(self):
        out = lint(
            "import jax\n"
            "def train(p, xs):\n"
            "    def objective(p):\n"
            "        print('tracing')\n"
            "        return (p * p).sum()\n"
            "    return jax.grad(objective)(p)\n", rules=["JIT001"])
        assert rule_ids(out) == ["JIT001"]

    def test_negative_pure_step_and_debug_callback(self):
        out = lint(
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def make():\n"
            "    def step(p, x):\n"
            "        jax.debug.print('loss {}', x)\n"
            "        jax.debug.callback(print, x)\n"
            "        local = []\n"
            "        local.append(x)\n"
            "        k = jax.random.PRNGKey(0)\n"
            "        noise = jax.random.normal(k, x.shape)\n"
            "        return p + jnp.sum(x) + noise\n"
            "    return jax.jit(step, donate_argnums=(0,))\n",
            rules=["JIT001"])
        assert out == []

    def test_negative_impure_outside_jit(self):
        out = lint(
            "import time\n"
            "def host_loop():\n"
            "    print('ok')\n"
            "    return time.time()\n", rules=["JIT001"])
        assert out == []

    def test_else_branch_global_write_is_not_lazy_init(self):
        # regression: the lazy-singleton exemption once keyed on the
        # ``if X is None:`` merely being an ANCESTOR — a write in the
        # else branch runs exactly when the cache is already set,
        # i.e. on every retrace
        out = lint(
            "import jax\n"
            "_CACHE = None\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    global _CACHE\n"
            "    if _CACHE is None:\n"
            "        pass\n"
            "    else:\n"
            "        _CACHE = x + 1\n"
            "    return x\n", rules=["JIT001"])
        assert rule_ids(out) == ["JIT001"]
        assert "global '_CACHE'" in out[0].message


# =============================================================== SYNC002


class TestSYNC002:
    HOT_LOOP = (
        "import jax\n"
        "import numpy as np\n"
        "step = jax.jit(lambda p, b: (p, p.sum()))\n"
        "def train_loop(p, batches):\n"
        "    for b in batches:\n"
        "        p, loss = step(p, b)\n"
        "        {body}\n"
        "    return p\n")

    def test_float_cast_in_hot_loop(self):
        out = lint(self.HOT_LOOP.format(body="l = float(loss)"),
                   rules=["SYNC002"])
        assert rule_ids(out) == ["SYNC002"]
        assert "float(loss)" in out[0].message

    def test_item_in_hot_loop(self):
        out = lint(self.HOT_LOOP.format(body="l = loss.item()"),
                   rules=["SYNC002"])
        assert rule_ids(out) == ["SYNC002"]

    def test_asarray_in_hot_loop(self):
        out = lint(self.HOT_LOOP.format(body="l = np.asarray(loss)"),
                   rules=["SYNC002"])
        assert rule_ids(out) == ["SYNC002"]

    def test_branch_on_traced_value_in_hot_loop(self):
        out = lint(self.HOT_LOOP.format(
            body="if loss:\n            p = p"), rules=["SYNC002"])
        assert rule_ids(out) == ["SYNC002"]
        assert "branching" in out[0].message

    def test_negative_sync_outside_loop(self):
        out = lint(
            "import jax\n"
            "step = jax.jit(lambda p, b: (p, p.sum()))\n"
            "def train_loop(p, batches):\n"
            "    for b in batches:\n"
            "        p, loss = step(p, b)\n"
            "    return p, float(loss)\n", rules=["SYNC002"])
        assert out == []

    def test_negative_nested_def_does_not_taint_outer_names(self):
        # helper's `total = model(x)` is a DIFFERENT scope: the outer
        # loop's host-literal `total` must not be flagged
        out = lint(
            "def train_loop(model, xs):\n"
            "    def helper(x):\n"
            "        total = model(x)\n"
            "        return total\n"
            "    for x in xs:\n"
            "        total = 0.0\n"
            "        v = float(total)\n"
            "    return v\n", rules=["SYNC002"])
        assert out == []

    def test_negative_host_values_and_cold_functions(self):
        out = lint(
            "import time\n"
            "def train_loop(xs):\n"
            "    for x in xs:\n"
            "        t = time.perf_counter()\n"
            "        wall = float(t)\n"       # host clock: fine
            "def helper(xs):\n"               # not a hot name
            "    for x in xs:\n"
            "        v = float(x)\n", rules=["SYNC002"])
        assert out == []


# ============================================================ COMPILE003


class TestCOMPILE003:
    def test_jit_inside_loop(self):
        out = lint(
            "import jax\n"
            "def train(xs):\n"
            "    for x in xs:\n"
            "        f = jax.jit(lambda a: a + 1)\n"
            "        f(x)\n", rules=["COMPILE003"])
        assert rule_ids(out) == ["COMPILE003"]
        assert "inside a loop" in out[0].message

    def test_fstring_on_traced_value(self):
        out = lint(
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    msg = f'value {x}'\n"
            "    return x\n", rules=["COMPILE003"])
        assert rule_ids(out) == ["COMPILE003"]
        assert "f-string" in out[0].message

    def test_shape_derived_traced_arg(self):
        out = lint(
            "import jax\n"
            "g = jax.jit(lambda a, n: a * n)\n"
            "def predict(batches):\n"
            "    for b in batches:\n"
            "        out = g(b, b.shape[0])\n"
            "    return out\n", rules=["COMPILE003"])
        assert rule_ids(out) == ["COMPILE003"]
        assert "shape-derived" in out[0].message

    def test_shape_derived_arg_to_decorator_jitted(self):
        out = lint(
            "import jax\n"
            "@jax.jit\n"
            "def g(a, n):\n"
            "    return a * n\n"
            "def predict(batches):\n"
            "    for b in batches:\n"
            "        out = g(b, b.shape[0])\n"
            "    return out\n", rules=["COMPILE003"])
        assert rule_ids(out) == ["COMPILE003"]

    def test_negative_static_argnums_declared(self):
        out = lint(
            "import jax\n"
            "g = jax.jit(lambda a, n: a * n, static_argnums=(1,))\n"
            "def predict(batches):\n"
            "    for b in batches:\n"
            "        out = g(b, b.shape[0])\n"
            "    return out\n", rules=["COMPILE003"])
        assert out == []

    def test_negative_jit_at_module_scope(self):
        out = lint(
            "import jax\n"
            "f = jax.jit(lambda a: a + 1)\n"
            "def train(xs):\n"
            "    return [f(x) for x in xs]\n", rules=["COMPILE003"])
        assert out == []

    def test_else_branch_jit_build_in_loop_is_not_memoized(self):
        # regression: the memoized-build exemption once keyed on the
        # ``if step is None:`` merely being an ANCESTOR — a build in
        # the else branch runs on every iteration after the first
        out = lint(
            "import jax\n"
            "def run(xs):\n"
            "    step = None\n"
            "    for x in xs:\n"
            "        if step is None:\n"
            "            pass\n"
            "        else:\n"
            "            step = jax.jit(lambda v: v + 1)\n"
            "        x = step(x)\n"
            "    return xs\n", rules=["COMPILE003"])
        assert rule_ids(out) == ["COMPILE003"]
        assert "inside a loop" in out[0].message


# ============================================================ COMPILE011


def lint_at(path, src, rules=None):
    """Like ``lint`` but at an explicit repo-relative path —
    COMPILE011 is path-scoped (only ``analytics_zoo_tpu/`` outside
    ``compile/`` is gated)."""
    from analytics_zoo_tpu.analysis.core import analyze_source
    return analyze_source(src, path=path, rule_ids=rules)


class TestCOMPILE011:
    SRC_DIRECT = (
        "import jax\n"
        "f = jax.jit(lambda x: x + 1)\n")

    def test_direct_jit_inside_package_fires(self):
        out = lint_at("analytics_zoo_tpu/models/m.py", self.SRC_DIRECT,
                      rules=["COMPILE011"])
        assert rule_ids(out) == ["COMPILE011"]
        assert out[0].severity == "error"
        assert "engine_jit" in out[0].message

    def test_decorator_forms_fire(self):
        out = lint_at(
            "analytics_zoo_tpu/models/m.py",
            "import jax\n"
            "from functools import partial\n"
            "@jax.jit\n"
            "def g(x):\n"
            "    return x * 2\n"
            "@partial(jax.jit, static_argnums=(1,))\n"
            "def h(x, n):\n"
            "    return x * n\n"
            "@jax.jit\n"  # zoolint fixture: call-form via visit_Call
            "def k(x):\n"
            "    return x\n", rules=["COMPILE011"])
        assert rule_ids(out) == ["COMPILE011"] * 3

    def test_pjit_and_from_import_fire(self):
        out = lint_at(
            "analytics_zoo_tpu/ops/m.py",
            "from jax import jit\n"
            "from jax.experimental.pjit import pjit\n"
            "a = jit(lambda x: x)\n"
            "b = pjit(lambda x: x)\n", rules=["COMPILE011"])
        assert rule_ids(out) == ["COMPILE011"] * 2

    def test_engine_jit_is_clean(self):
        out = lint_at(
            "analytics_zoo_tpu/models/m.py",
            "from analytics_zoo_tpu.compile import engine_jit\n"
            "f = engine_jit(lambda x: x + 1, key_hint='f')\n",
            rules=["COMPILE011"])
        assert out == []

    def test_compile_package_itself_exempt(self):
        out = lint_at("analytics_zoo_tpu/compile/engine.py",
                      self.SRC_DIRECT, rules=["COMPILE011"])
        assert out == []

    def test_examples_and_tests_exempt(self):
        for path in ("examples/quickstart/demo.py",
                     "tests/test_something.py",
                     "scripts/tool.py"):
            assert lint_at(path, self.SRC_DIRECT,
                           rules=["COMPILE011"]) == []

    def test_inline_suppression(self):
        out = lint_at(
            "analytics_zoo_tpu/ops/m.py",
            "import jax\n"
            "# zoolint: disable=COMPILE011 — capability probe\n"
            "f = jax.jit(lambda x: x)\n", rules=["COMPILE011"])
        assert out == []

    def test_rule_coverage_survives_the_chokepoint(self):
        """Converting a site to engine_jit must NOT lose the other
        rules' coverage: an impure function built through the
        chokepoint still fires JIT001, and an undonated opt_state
        thread still fires DONATE004."""
        out = lint_at(
            "analytics_zoo_tpu/models/m.py",
            "from analytics_zoo_tpu.compile import engine_jit\n"
            "def step(params, opt_state, x):\n"
            "    print('hi')\n"
            "    return params, opt_state\n"
            "jitted = engine_jit(step)\n",
            rules=["JIT001", "DONATE004", "COMPILE011"])
        assert sorted(rule_ids(out)) == ["DONATE004", "JIT001"]


# ============================================================= DONATE004


class TestDONATE004:
    def test_train_step_without_donation(self):
        out = lint(
            "import jax\n"
            "def build():\n"
            "    def step(params, opt_state, batch):\n"
            "        return params, opt_state\n"
            "    return jax.jit(step)\n", rules=["DONATE004"])
        assert rule_ids(out) == ["DONATE004"]
        assert "donate_argnums" in out[0].message

    def test_decorator_forms(self):
        out = lint(
            "import jax\n"
            "from functools import partial\n"
            "@jax.jit\n"
            "def step(params, opt_state, batch):\n"
            "    return params, opt_state\n"
            "@partial(jax.jit, static_argnums=(2,))\n"
            "def step2(params, opt_state, n):\n"
            "    return params, opt_state\n"
            "@partial(jax.jit, donate_argnums=(0, 1))\n"
            "def step3(params, opt_state, batch):\n"
            "    return params, opt_state\n", rules=["DONATE004"])
        assert len(out) == 2
        assert {f.symbol for f in out} == {"step", "step2"}

    def test_negative_donated_and_stateless(self):
        out = lint(
            "import jax\n"
            "def build():\n"
            "    def step(params, opt_state, batch):\n"
            "        return params, opt_state\n"
            "    def eval_step(params, state, batch):\n"
            "        return params\n"
            "    return (jax.jit(step, donate_argnums=(0, 1)),\n"
            "            jax.jit(eval_step))\n", rules=["DONATE004"])
        assert out == []


# =============================================================== RACE005


class TestRACE005:
    THREADED = (
        "import threading\n"
        "_CACHE = {}\n"
        "_LOCK = threading.Lock()\n"
        "def reader():\n"
        "    return _CACHE.get('x')\n")

    def test_unlocked_write_in_threaded_module(self):
        out = lint(self.THREADED +
                   "def writer(k, v):\n"
                   "    _CACHE[k] = v\n", rules=["RACE005"])
        assert rule_ids(out) == ["RACE005"]
        assert "_CACHE" in out[0].message
        assert out[0].severity == "error"

    def test_unlocked_global_rebind(self):
        out = lint(
            "import threading\n"
            "_STATE = None\n"
            "def get_state():\n"
            "    global _STATE\n"
            "    if _STATE is None:\n"
            "        _STATE = object()\n"
            "    return _STATE\n", rules=["RACE005"])
        assert rule_ids(out) == ["RACE005"]

    def test_negative_locked_write(self):
        out = lint(self.THREADED +
                   "def writer(k, v):\n"
                   "    with _LOCK:\n"
                   "        _CACHE[k] = v\n", rules=["RACE005"])
        assert out == []

    def test_negative_local_shadow_is_not_shared_state(self):
        out = lint(self.THREADED +
                   "def shadowing():\n"
                   "    _CACHE = {}\n"
                   "    _CACHE['x'] = 1\n"
                   "    _CACHE['x'] += 1\n"
                   "    del _CACHE['x']\n"
                   "    return _CACHE\n", rules=["RACE005"])
        assert out == []

    def test_negative_unthreaded_module(self):
        out = lint(
            "_CACHE = {}\n"
            "def reader():\n"
            "    return _CACHE.get('x')\n"
            "def writer(k, v):\n"
            "    _CACHE[k] = v\n", rules=["RACE005"])
        assert out == []


# ================================================================ RNG006


class TestRNG006:
    def test_key_consumed_twice(self):
        out = lint(
            "import jax\n"
            "def sample(key):\n"
            "    a = jax.random.normal(key, (3,))\n"
            "    b = jax.random.uniform(key, (3,))\n"
            "    return a + b\n", rules=["RNG006"])
        assert rule_ids(out) == ["RNG006"]
        assert "already consumed" in out[0].message

    def test_rng_kwarg_reuse(self):
        out = lint(
            "def call(model, x, rng):\n"
            "    f = model.apply(x, rng=rng)\n"
            "    b = model.apply(x, rng=rng)\n"
            "    return f + b\n", rules=["RNG006"])
        assert rule_ids(out) == ["RNG006"]

    def test_negative_fully_terminating_trailing_if(self):
        # the consuming branch ends in an If BOTH of whose arms
        # raise — nothing falls through to the final consumption, so
        # the key is used once per executed path (regression:
        # _terminates only looked at the last statement's type)
        out = lint(
            "import jax\n"
            "def f(rng, c):\n"
            "    if c:\n"
            "        x = jax.random.normal(rng, (2,))\n"
            "        if x.sum() > 0:\n"
            "            raise ValueError()\n"
            "        else:\n"
            "            raise KeyError()\n"
            "    return jax.random.normal(rng, (2,))\n",
            rules=["RNG006"])
        assert out == []

    def test_consumption_in_loop_iterable_counts(self):
        out = lint(
            "import jax\n"
            "def sample(key, xs):\n"
            "    for p in jax.random.permutation(key, xs):\n"
            "        pass\n"
            "    return jax.random.normal(key, (3,))\n",
            rules=["RNG006"])
        assert rule_ids(out) == ["RNG006"]

    def test_negative_loop_target_rebinds_each_iteration(self):
        out = lint(
            "import jax\n"
            "def sample(key, n):\n"
            "    out = []\n"
            "    for k in jax.random.split(key, n):\n"
            "        out.append(jax.random.normal(k, (3,)))\n"
            "    return out\n", rules=["RNG006"])
        assert out == []

    def test_loop_reuse_without_fold_in(self):
        out = lint(
            "import jax\n"
            "def sample(key, xs):\n"
            "    out = []\n"
            "    for x in xs:\n"
            "        out.append(jax.random.normal(key, (3,)))\n"
            "    return out\n", rules=["RNG006"])
        assert rule_ids(out) == ["RNG006"]

    def test_negative_split_and_fold_in(self):
        out = lint(
            "import jax\n"
            "def sample(key, xs):\n"
            "    k1, k2 = jax.random.split(key)\n"
            "    a = jax.random.normal(k1, (3,))\n"
            "    b = jax.random.uniform(k2, (3,))\n"
            "    out = []\n"
            "    for i, x in enumerate(xs):\n"
            "        k = jax.random.fold_in(key, i)\n"
            "        out.append(jax.random.normal(k, (3,)))\n"
            "    return a + b, out\n", rules=["RNG006"])
        assert out == []

    def test_subscript_target_is_not_a_rebind(self):
        # ``out[rng] = a`` READS rng; it must not re-arm the key
        out = lint(
            "import jax\n"
            "def sample(rng, out):\n"
            "    a = jax.random.normal(rng, (2,))\n"
            "    out[rng] = a\n"
            "    b = jax.random.normal(rng, (2,))\n"
            "    return b\n", rules=["RNG006"])
        assert rule_ids(out) == ["RNG006"]

    def test_continue_branch_still_reuses_across_iterations(self):
        # ``continue`` re-enters the loop header — the key consumed
        # before it is consumed AGAIN next iteration (unlike
        # return/break, which leave the path entirely)
        out = lint(
            "import jax\n"
            "def sample(rng, xs):\n"
            "    out = []\n"
            "    for x in xs:\n"
            "        if x > 0:\n"
            "            out.append(jax.random.normal(rng, (2,)))\n"
            "            continue\n"
            "        out.append(x)\n"
            "    return out\n", rules=["RNG006"])
        assert rule_ids(out) == ["RNG006"]

    def test_negative_break_branch_cannot_pair_with_later_iterations(self):
        out = lint(
            "import jax\n"
            "def sample(rng, xs):\n"
            "    for x in xs:\n"
            "        if x > 0:\n"
            "            y = jax.random.normal(rng, (2,))\n"
            "            break\n"
            "    return xs\n", rules=["RNG006"])
        assert out == []

    def test_break_branch_pairs_with_post_loop_use(self):
        # regression: a break path leaves the loop BODY but still
        # reaches the code after the loop — consume-before-break +
        # consume-after-loop is the same key twice on that path
        out = lint(
            "import jax\n"
            "def sample(rng, xs):\n"
            "    a = None\n"
            "    for x in xs:\n"
            "        if x > 0:\n"
            "            a = jax.random.normal(rng, (2,))\n"
            "            break\n"
            "    b = jax.random.normal(rng, (2,))\n"
            "    return a, b\n", rules=["RNG006"])
        assert rule_ids(out) == ["RNG006"]
        assert "already consumed" in out[0].message

    def test_negative_split_before_break_rearms_post_loop_use(self):
        out = lint(
            "import jax\n"
            "def sample(rng, xs):\n"
            "    for x in xs:\n"
            "        if x > 0:\n"
            "            rng, sub = jax.random.split(rng)\n"
            "            a = jax.random.normal(sub, (2,))\n"
            "            break\n"
            "    return jax.random.normal(rng, (2,))\n",
            rules=["RNG006"])
        assert out == []

    def test_negative_one_use_per_branch(self):
        out = lint(
            "import jax\n"
            "def sample(key, flag):\n"
            "    if flag:\n"
            "        return jax.random.normal(key, (3,))\n"
            "    else:\n"
            "        return jax.random.uniform(key, (3,))\n",
            rules=["RNG006"])
        assert out == []


# ==================================================== interprocedural layer


class TestInterprocedural:
    def test_jit001_sees_through_helper_calls(self):
        # the print lives in a helper CALLED FROM the jitted step —
        # invisible to PR 5's intraprocedural pass
        out = lint(
            "import jax\n"
            "def log_stats(x):\n"
            "    print('stats', x)\n"
            "@jax.jit\n"
            "def step(p, x):\n"
            "    log_stats(x)\n"
            "    return p * x\n", rules=["JIT001"])
        assert rule_ids(out) == ["JIT001"]
        assert out[0].symbol == "log_stats"

    def test_jit001_through_self_method_and_bound_lambda(self):
        out = lint(
            "import jax\n"
            "import time\n"
            "class Trainer:\n"
            "    def _core(self, p, b):\n"
            "        t = time.time()\n"
            "        return p + t\n"
            "    def build(self):\n"
            "        fn = lambda p, b: self._core(p, b)\n"
            "        return jax.jit(fn)\n", rules=["JIT001"])
        assert rule_ids(out) == ["JIT001"]
        assert out[0].symbol == "Trainer._core"

    def test_jit001_negative_sibling_lambda_stays_host(self):
        # two lambdas in one function share a '<qual>.<lambda>'-style
        # qualname unless disambiguated — jitting the second must not
        # force-trace the host-only first (regression: the clock read
        # in 'host' was flagged as inside-jit)
        out = lint(
            "import jax\n"
            "import time\n"
            "def build():\n"
            "    host = lambda: time.time()\n"
            "    fn = lambda p: p + 1\n"
            "    step = jax.jit(fn)\n"
            "    t = host()\n"
            "    return step, t\n", rules=["JIT001"])
        assert out == []

    def test_jit001_negative_callback_arg_is_host(self):
        # the helper reaches the trace only through debug.callback —
        # it runs on HOST, not at trace time
        out = lint(
            "import jax\n"
            "import time\n"
            "def record(x):\n"
            "    return time.time()\n"
            "@jax.jit\n"
            "def step(p):\n"
            "    jax.debug.callback(record, p)\n"
            "    return p\n", rules=["JIT001"])
        assert out == []

    def test_sync002_sees_item_inside_helper(self):
        out = lint(
            "import jax\n"
            "step = jax.jit(lambda p, b: (p, p.sum()))\n"
            "def log_loss(loss):\n"
            "    return loss.item()\n"
            "def train_loop(p, batches):\n"
            "    for b in batches:\n"
            "        p, loss = step(p, b)\n"
            "        log_loss(loss)\n"
            "    return p\n", rules=["SYNC002"])
        assert rule_ids(out) == ["SYNC002"]
        assert out[0].symbol == "log_loss"

    def test_sync002_negative_helper_outside_loop(self):
        out = lint(
            "import jax\n"
            "step = jax.jit(lambda p, b: (p, p.sum()))\n"
            "def log_loss(loss):\n"
            "    return loss.item()\n"
            "def train_loop(p, batches):\n"
            "    for b in batches:\n"
            "        p, loss = step(p, b)\n"
            "    log_loss(loss)\n"
            "    return p\n", rules=["SYNC002"])
        assert out == []

    def test_rng006_key_consumed_by_two_helpers(self):
        out = lint(
            "import jax\n"
            "def sample_a(k):\n"
            "    return jax.random.normal(k, (3,))\n"
            "def sample_b(k):\n"
            "    return jax.random.uniform(k, (3,))\n"
            "def draw(key):\n"
            "    a = sample_a(key)\n"
            "    b = sample_b(key)\n"
            "    return a + b\n", rules=["RNG006"])
        assert rule_ids(out) == ["RNG006"]
        assert "key" in out[0].message

    def test_rng006_negative_helper_only_derives(self):
        out = lint(
            "import jax\n"
            "def derive(k, n):\n"
            "    return jax.random.split(k, n)\n"
            "def draw(key):\n"
            "    k1, k2 = derive(key, 2)\n"
            "    a = jax.random.normal(k1, (3,))\n"
            "    b = jax.random.normal(k2, (3,))\n"
            "    return a + b\n", rules=["RNG006"])
        assert out == []

    def test_rng006_negative_early_return_branch(self):
        # ``if small: return normal(rng)`` never falls through — the
        # second use is NOT a reuse (the orthogonal-init pattern)
        out = lint(
            "import jax\n"
            "def normal(rng, shape):\n"
            "    return jax.random.normal(rng, shape)\n"
            "def init(rng, shape):\n"
            "    if len(shape) < 2:\n"
            "        return normal(rng, shape)\n"
            "    return jax.random.normal(rng, (max(shape), 2))\n",
            rules=["RNG006"])
        assert out == []

    def test_jit001_negative_lazy_singleton_getter(self):
        # ``global X; if X is None: X = ctor()`` memoizes HOST state —
        # the platform's get_config/get_policy idiom, callable at
        # trace time by convention
        out = lint(
            "import jax\n"
            "_CFG = None\n"
            "def get_cfg():\n"
            "    global _CFG\n"
            "    if _CFG is None:\n"
            "        _CFG = object()\n"
            "    return _CFG\n"
            "@jax.jit\n"
            "def step(p):\n"
            "    cfg = get_cfg()\n"
            "    return p\n", rules=["JIT001"])
        assert out == []

    def test_compile003_negative_memoized_jit_in_hot_helper(self):
        # built under ``if self._step is None:`` — compiles once no
        # matter how hot the caller is
        out = lint(
            "import jax\n"
            "class Est:\n"
            "    def __init__(self):\n"
            "        self._step = None\n"
            "    def evaluate(self, b):\n"
            "        if self._step is None:\n"
            "            self._step = jax.jit(lambda x: x + 1)\n"
            "        return self._step(b)\n"
            "    def fit(self, batches):\n"
            "        for b in batches:\n"
            "            self.evaluate(b)\n", rules=["COMPILE003"])
        assert out == []

    def test_donation_spec_visible_across_modules(self, tmp_path):
        # a jitted callable imported from another analyzed module
        # carries its (lack of) static_argnums into COMPILE003
        (tmp_path / "steps.py").write_text(
            "import jax\n"
            "g = jax.jit(lambda a, n: a * n)\n")
        (tmp_path / "loop.py").write_text(
            "from steps import g\n"
            "def predict(batches):\n"
            "    for b in batches:\n"
            "        out = g(b, b.shape[0])\n"
            "    return out\n")
        from analytics_zoo_tpu.analysis import analyze_paths
        findings, errors = analyze_paths(
            [str(tmp_path)], root=str(tmp_path),
            rule_ids=["COMPILE003"])
        assert errors == []
        assert rule_ids(findings) == ["COMPILE003"]
        assert "shape-derived" in findings[0].message


# ================================================================ SHARD007


class TestSHARD007:
    def test_unknown_axis_flagged_against_canonical_universe(self):
        out = lint(
            "from jax.sharding import PartitionSpec as P\n"
            "spec = P('data', 'modle')\n", rules=["SHARD007"])
        assert rule_ids(out) == ["SHARD007"]
        assert "'modle'" in out[0].message

    def test_axis_constants_and_project_meshes_define_universe(self):
        # a custom Mesh literal adds its axes; the *_AXIS constant
        # resolves through the project's constant index
        out = lint(
            "import numpy as np\n"
            "from jax.sharding import Mesh, PartitionSpec as P\n"
            "RING_AXIS = 'ring'\n"
            "mesh = Mesh(np.array([[0]]), ('ring', 'lane'))\n"
            "a = P(RING_AXIS)\n"
            "b = P('lane', None)\n", rules=["SHARD007"])
        assert out == []

    def test_shard_map_full_replication_of_params(self):
        out = lint(
            "import jax\n"
            "from jax.sharding import PartitionSpec as P\n"
            "def body(params, x):\n"
            "    return params @ x\n"
            "def build(mesh):\n"
            "    return jax.shard_map(body, mesh=mesh,\n"
            "                         in_specs=(P(), P('data')),\n"
            "                         out_specs=P('data'))\n",
            rules=["SHARD007"])
        assert rule_ids(out) == ["SHARD007"]
        assert "replicated" in out[0].message

    def test_shard_map_negative_sharded_params_and_small_args(self):
        out = lint(
            "import jax\n"
            "from jax.sharding import PartitionSpec as P\n"
            "def body(params, scale):\n"
            "    return params * scale\n"
            "def build(mesh):\n"
            "    return jax.shard_map(body, mesh=mesh,\n"
            "                         in_specs=(P('model'), P()),\n"
            "                         out_specs=P('model'))\n",
            rules=["SHARD007"])
        # params is sharded; ``scale`` is not a large-param name
        assert out == []

    def test_spec_construction_in_hot_loop(self):
        out = lint(
            "import jax\n"
            "from jax.sharding import NamedSharding, PartitionSpec as P\n"
            "def train_loop(mesh, batches):\n"
            "    for b in batches:\n"
            "        sh = NamedSharding(mesh, P('data'))\n"
            "        jax.device_put(b, sh)\n", rules=["SHARD007"])
        assert [f.rule for f in out].count("SHARD007") >= 1
        assert "hot loop" in out[0].message

    def test_negative_spec_built_outside_loop(self):
        out = lint(
            "import jax\n"
            "from jax.sharding import NamedSharding, PartitionSpec as P\n"
            "def train_loop(mesh, batches):\n"
            "    sh = NamedSharding(mesh, P('data'))\n"
            "    for b in batches:\n"
            "        jax.device_put(b, sh)\n", rules=["SHARD007"])
        assert out == []

    def test_conflicting_sharding_constraints(self):
        out = lint(
            "import jax\n"
            "from jax.sharding import PartitionSpec as P\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    x = jax.lax.with_sharding_constraint(x, P('data'))\n"
            "    x = x * 2\n"
            "    x = jax.lax.with_sharding_constraint(x, P('model'))\n"
            "    return x\n", rules=["SHARD007"])
        assert rule_ids(out) == ["SHARD007"]
        assert "reshard" in out[0].message

    def test_negative_constraints_in_exclusive_branches(self):
        # opposite arms of one ``if`` — only one constraint executes
        # per (static-arg-specialized) trace, so there is no reshard
        # between them
        out = lint(
            "import jax\n"
            "from jax.sharding import PartitionSpec as P\n"
            "@jax.jit\n"
            "def step(x, c):\n"
            "    if c:\n"
            "        x = jax.lax.with_sharding_constraint(x, P('data'))\n"
            "    else:\n"
            "        x = jax.lax.with_sharding_constraint(x, P('model'))\n"
            "    return x\n", rules=["SHARD007"])
        assert out == []

    def test_negative_repeated_identical_constraint(self):
        out = lint(
            "import jax\n"
            "from jax.sharding import PartitionSpec as P\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    x = jax.lax.with_sharding_constraint(x, P('data'))\n"
            "    x = x * 2\n"
            "    x = jax.lax.with_sharding_constraint(x, P('data'))\n"
            "    return x\n", rules=["SHARD007"])
        assert out == []


# ================================================================= MEM009


class TestMEM009:
    def test_dead_state_through_non_donating_jit_call(self):
        out = lint(
            "import jax\n"
            "step = jax.jit(lambda p, o, b: (p, o))\n"
            "def train(params, opt_state, batches):\n"
            "    for b in batches:\n"
            "        params, opt_state = step(params, opt_state, b)\n"
            "    return params\n", rules=["MEM009"])
        assert rule_ids(out) == ["MEM009"]
        assert "donate_argnums" in out[0].message

    def test_negative_donating_jit_call(self):
        out = lint(
            "import jax\n"
            "step = jax.jit(lambda p, o, b: (p, o),\n"
            "               donate_argnums=(0, 1))\n"
            "def train(params, opt_state, batches):\n"
            "    for b in batches:\n"
            "        params, opt_state = step(params, opt_state, b)\n"
            "    return params\n", rules=["MEM009"])
        assert out == []

    def test_unbounded_device_accumulation_in_hot_loop(self):
        out = lint(
            "import jax\n"
            "predict_step = jax.jit(lambda p, b: p @ b)\n"
            "def predict(p, batches):\n"
            "    outs = []\n"
            "    for b in batches:\n"
            "        outs.append(predict_step(p, b))\n"
            "    return outs\n", rules=["MEM009"])
        assert rule_ids(out) == ["MEM009"]
        assert "HBM" in out[0].message

    def test_negative_bounded_window_with_flush(self):
        # the PR 5 predict pattern: window-8 sliding device_get
        out = lint(
            "import jax\n"
            "predict_step = jax.jit(lambda p, b: p @ b)\n"
            "def predict(p, batches):\n"
            "    outs, window = [], []\n"
            "    for b in batches:\n"
            "        window.append(predict_step(p, b))\n"
            "        if len(window) >= 8:\n"
            "            outs.append(jax.device_get(window.pop(0)))\n"
            "    outs.extend(jax.device_get(window))\n"
            "    return outs\n", rules=["MEM009"])
        assert out == []

    def test_negative_host_values_accumulate_fine(self):
        out = lint(
            "def predict(batches):\n"
            "    outs = []\n"
            "    for b in batches:\n"
            "        outs.append(len(b))\n"
            "    return outs\n", rules=["MEM009"])
        assert out == []

    def test_negative_host_pull_rebind_before_append(self):
        # regression: the reaching binding is the LATEST one before
        # the append — ``x = step(...); x = np.asarray(x)`` appends a
        # host array, not the jitted output
        out = lint(
            "import jax\n"
            "import numpy as np\n"
            "step = jax.jit(lambda p, b: p @ b)\n"
            "def predict(p, batches):\n"
            "    outs = []\n"
            "    for b in batches:\n"
            "        x = step(p, b)\n"
            "        x = np.asarray(x)\n"
            "        outs.append(x)\n"
            "    return outs\n", rules=["MEM009"])
        assert out == []

    def test_device_rebind_after_host_binding_still_fires(self):
        # mirror image of the host-pull rebind: the binding reaching
        # the append is the jitted call, whatever came first
        out = lint(
            "import jax\n"
            "step = jax.jit(lambda p, b: p @ b)\n"
            "def predict(p, batches):\n"
            "    outs = []\n"
            "    for b in batches:\n"
            "        x = b\n"
            "        x = step(p, b)\n"
            "        outs.append(x)\n"
            "    return outs\n", rules=["MEM009"])
        assert rule_ids(out) == ["MEM009"]

    def test_donation_must_cover_the_rebound_state_args(self):
        # regression: mere PRESENCE of donate_argnums once exempted
        # the call site — donating only the batch arg leaves both
        # state trees live
        out = lint(
            "import jax\n"
            "step = jax.jit(lambda p, o, b: (p, o),\n"
            "               donate_argnums=(2,))\n"
            "def train(params, opt_state, batches):\n"
            "    for b in batches:\n"
            "        params, opt_state = step(params, opt_state, b)\n"
            "    return params\n", rules=["MEM009"])
        assert rule_ids(out) == ["MEM009"]
        assert "position 0" in out[0].message

    def test_partial_donation_coverage_across_modules(self, tmp_path):
        # the fact bundle must carry the LITERAL donate positions,
        # not a declared-donation boolean — donating only the batch
        # in the defining module leaves both state trees live at the
        # importing call site (regression: cross-module partial
        # donation was silently assumed covered)
        (tmp_path / "steps.py").write_text(
            "import jax\n"
            "step = jax.jit(lambda p, o, b: (p, o),\n"
            "               donate_argnums=(2,))\n")
        (tmp_path / "loop.py").write_text(
            "from steps import step\n"
            "def fit(params, opt_state, batches):\n"
            "    for b in batches:\n"
            "        params, opt_state = step(params, opt_state, b)\n"
            "    return params\n")
        from analytics_zoo_tpu.analysis import analyze_paths
        findings, errors = analyze_paths(
            [str(tmp_path)], root=str(tmp_path), rule_ids=["MEM009"])
        assert errors == []
        assert rule_ids(findings) == ["MEM009"]
        assert "position 0" in findings[0].message
        # full coverage in the defining module stays clean
        (tmp_path / "steps.py").write_text(
            "import jax\n"
            "step = jax.jit(lambda p, o, b: (p, o),\n"
            "               donate_argnums=(0, 1))\n")
        findings, errors = analyze_paths(
            [str(tmp_path)], root=str(tmp_path), rule_ids=["MEM009"])
        assert errors == []
        assert findings == []

    def test_negative_single_int_donate_argnums_covers_state(self):
        out = lint(
            "import jax\n"
            "update = jax.jit(lambda o, g: o, donate_argnums=0)\n"
            "def train(opt_state, grads_list):\n"
            "    for g in grads_list:\n"
            "        opt_state = update(opt_state, g)\n"
            "    return opt_state\n", rules=["MEM009"])
        assert out == []

    def test_negative_eager_call_to_raw_wrapped_function(self):
        # regression: ``step = jax.jit(helper)`` once registered
        # 'helper' itself as a jit call site — a debug/eager path
        # calling helper() directly was flagged for donation, where
        # donation semantics don't apply at all
        out = lint(
            "import jax\n"
            "def helper(params, opt_state, b):\n"
            "    return params, opt_state\n"
            "step = jax.jit(helper, donate_argnums=(0, 1))\n"
            "def debug_path(params, opt_state, batches):\n"
            "    for b in batches:\n"
            "        params, opt_state = helper(params, opt_state, b)\n"
            "    return params\n", rules=["MEM009", "COMPILE003"])
        assert out == []

    def test_self_rebound_jit_wrapper_still_counts(self):
        # ``helper = jax.jit(helper)`` makes the raw name THE
        # compiled callable — its call sites keep the donation check
        out = lint(
            "import jax\n"
            "def helper(params, opt_state, b):\n"
            "    return params, opt_state\n"
            "helper = jax.jit(helper)\n"
            "def train(params, opt_state, batches):\n"
            "    for b in batches:\n"
            "        params, opt_state = helper(params, opt_state, b)\n"
            "    return params\n", rules=["MEM009"])
        assert rule_ids(out) == ["MEM009"]


# ================================================================ LOCK010


class TestLOCK010:
    def test_inconsistent_lock_order_across_functions(self):
        out = lint(
            "import threading\n"
            "_A = threading.Lock()\n"
            "_B = threading.Lock()\n"
            "def one():\n"
            "    with _A:\n"
            "        with _B:\n"
            "            return 1\n"
            "def two():\n"
            "    with _B:\n"
            "        with _A:\n"
            "            return 2\n", rules=["LOCK010"])
        assert len(out) == 2
        assert all(f.rule == "LOCK010" for f in out)
        assert "inconsistent lock order" in out[0].message

    def test_negative_consistent_order(self):
        out = lint(
            "import threading\n"
            "_A = threading.Lock()\n"
            "_B = threading.Lock()\n"
            "def one():\n"
            "    with _A:\n"
            "        with _B:\n"
            "            return 1\n"
            "def two():\n"
            "    with _A:\n"
            "        with _B:\n"
            "            return 2\n", rules=["LOCK010"])
        assert out == []

    def test_self_deadlock_through_call_chain(self):
        out = lint(
            "import threading\n"
            "_LOCK = threading.Lock()\n"
            "def inner():\n"
            "    with _LOCK:\n"
            "        return 1\n"
            "def outer():\n"
            "    with _LOCK:\n"
            "        return inner()\n", rules=["LOCK010"])
        assert rule_ids(out) == ["LOCK010"]
        assert "self-deadlock" in out[0].message

    def test_negative_rlock_reentry_is_fine(self):
        out = lint(
            "import threading\n"
            "_LOCK = threading.RLock()\n"
            "def inner():\n"
            "    with _LOCK:\n"
            "        return 1\n"
            "def outer():\n"
            "    with _LOCK:\n"
            "        return inner()\n", rules=["LOCK010"])
        assert out == []

    def test_lock_held_across_blocking_calls(self):
        out = lint(
            "import queue\n"
            "import threading\n"
            "import time\n"
            "_LOCK = threading.Lock()\n"
            "q = queue.Queue()\n"
            "def drain():\n"
            "    with _LOCK:\n"
            "        item = q.get()\n"
            "        time.sleep(0.1)\n"
            "        return item\n", rules=["LOCK010"])
        assert len(out) == 2
        assert "blocking" in out[0].message

    def test_imported_rlock_keeps_identity_and_kind(self, tmp_path):
        # regression: an imported lock once minted a per-importer id —
        # the defining module's kind (rlock) was unknown there, so a
        # legal re-entry through a call chain read as self-deadlock
        (tmp_path / "locks.py").write_text(
            "import threading\n"
            "STATE_LOCK = threading.RLock()\n")
        (tmp_path / "user.py").write_text(
            "import threading\n"
            "from locks import STATE_LOCK\n"
            "def inner():\n"
            "    with STATE_LOCK:\n"
            "        return 1\n"
            "def outer():\n"
            "    with STATE_LOCK:\n"
            "        return inner()\n"
            "def spawn():\n"
            "    threading.Thread(target=outer).start()\n")
        from analytics_zoo_tpu.analysis import analyze_paths
        findings, errors = analyze_paths(
            [str(tmp_path)], root=str(tmp_path), rule_ids=["LOCK010"])
        assert errors == []
        assert findings == []

    def test_order_cycle_connects_across_importing_modules(
            self, tmp_path):
        # the flip side of per-importer ids: an A/B inversion split
        # over two modules importing the same locks must join into
        # ONE graph and fire
        (tmp_path / "locks.py").write_text(
            "import threading\n"
            "ORDER_A = threading.Lock()\n"
            "ORDER_B = threading.Lock()\n")
        (tmp_path / "m1.py").write_text(
            "import threading\n"
            "from locks import ORDER_A, ORDER_B\n"
            "def one():\n"
            "    with ORDER_A:\n"
            "        with ORDER_B:\n"
            "            return 1\n"
            "def spawn():\n"
            "    threading.Thread(target=one).start()\n")
        (tmp_path / "m2.py").write_text(
            "from locks import ORDER_A, ORDER_B\n"
            "def two():\n"
            "    with ORDER_B:\n"
            "        with ORDER_A:\n"
            "            return 2\n")
        from analytics_zoo_tpu.analysis import analyze_paths
        findings, errors = analyze_paths(
            [str(tmp_path)], root=str(tmp_path), rule_ids=["LOCK010"])
        assert errors == []
        assert rule_ids(findings) == ["LOCK010", "LOCK010"]
        assert {f.path for f in findings} == {"m1.py", "m2.py"}

    def test_every_held_lock_reported_across_blocking_call(self):
        # regression: only the INNERMOST held lock was reported —
        # fixing the inner scope went green while the outer lock was
        # still held across the wait
        out = lint(
            "import queue\n"
            "import threading\n"
            "_A = threading.Lock()\n"
            "_B = threading.Lock()\n"
            "_q = queue.Queue()\n"
            "def drain():\n"
            "    with _A:\n"
            "        with _B:\n"
            "            return _q.get()\n"
            "def spawn():\n"
            "    threading.Thread(target=drain).start()\n",
            rules=["LOCK010"])
        assert rule_ids(out) == ["LOCK010", "LOCK010"]
        assert {f.message.split("'")[1] for f in out} == {"_A", "_B"}

    def test_unrelated_lock_held_across_condition_wait(self):
        # regression: the cv-idiom exemption once keyed only on the
        # wait RECEIVER being a Condition — but wait() releases only
        # the condition's own lock; any other lock stays held for
        # the whole (unbounded) wait
        out = lint(
            "import threading\n"
            "class W:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._cv = threading.Condition()\n"
            "    def worker(self):\n"
            "        with self._lock:\n"
            "            with self._cv:\n"
            "                self._cv.wait()\n", rules=["LOCK010"])
        assert rule_ids(out) == ["LOCK010"]
        assert "_lock" in out[0].message
        assert "_cv' is held" not in out[0].message

    def test_lock_held_across_transitively_blocking_call(self):
        # regression: does-it-block must propagate through the call
        # graph — the sleep here is TWO resolvable hops below the
        # lock-holding frame
        out = lint(
            "import threading\n"
            "import time\n"
            "_LOCK = threading.Lock()\n"
            "def leaf():\n"
            "    time.sleep(5)\n"
            "def mid():\n"
            "    leaf()\n"
            "def serve():\n"
            "    with _LOCK:\n"
            "        mid()\n", rules=["LOCK010"])
        assert rule_ids(out) == ["LOCK010"]
        assert "blocks on" in out[0].message
        assert "via" in out[0].message

    def test_negative_condition_wait_and_dict_get(self):
        out = lint(
            "import threading\n"
            "_cv = threading.Condition()\n"
            "_LOCK = threading.Lock()\n"
            "_cache = {}\n"
            "def waiter():\n"
            "    with _cv:\n"
            "        _cv.wait()\n"
            "def reader(k):\n"
            "    with _LOCK:\n"
            "        return _cache.get(k, None)\n", rules=["LOCK010"])
        assert out == []

    def test_negative_function_local_locks_never_alias(self):
        # each call creates FRESH lock objects — two functions nesting
        # their own locals in opposite orders cannot deadlock
        out = lint(
            "import threading\n"
            "def one():\n"
            "    my_lock = threading.Lock()\n"
            "    other_lock = threading.Lock()\n"
            "    with my_lock:\n"
            "        with other_lock:\n"
            "            return 1\n"
            "def two():\n"
            "    my_lock = threading.Lock()\n"
            "    other_lock = threading.Lock()\n"
            "    with other_lock:\n"
            "        with my_lock:\n"
            "            return 2\n", rules=["LOCK010"])
        assert out == []

    def test_lock010_suppression_works(self):
        out = lint(
            "import threading\n"
            "import time\n"
            "_LOCK = threading.Lock()\n"
            "def slow():\n"
            "    with _LOCK:\n"
            "        # zoolint: disable=LOCK010 — deliberate\n"
            "        time.sleep(1)\n", rules=["LOCK010"])
        assert out == []


# ====================================================== framework semantics


class TestSuppression:
    SRC = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    print('hi'){suffix}\n"
        "    return x\n")

    def test_same_line_disable(self):
        out = lint(self.SRC.format(
            suffix="   # zoolint: disable=JIT001 — trace-time banner"))
        assert out == []

    def test_line_above_disable(self):
        out = lint(
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    # zoolint: disable=JIT001 — deliberate\n"
            "    print('hi')\n"
            "    return x\n")
        assert out == []

    def test_disable_all(self):
        out = lint(self.SRC.format(suffix="  # zoolint: disable=all"))
        assert out == []

    def test_wrong_rule_does_not_suppress(self):
        out = lint(self.SRC.format(
            suffix="  # zoolint: disable=SYNC002"))
        assert rule_ids(out) == ["JIT001"]

    def test_natural_language_reason_still_suppresses(self):
        out = lint(self.SRC.format(
            suffix="  # zoolint: disable=JIT001 because trace banner"))
        assert out == []

    # -- decorated defs: a suppression on EITHER the decorator line or
    # the def line covers findings reported at any line of the span
    # (the regression fixed in this PR: DONATE004 reports decorator-
    # form findings at the decorator line but def-scoped ones at the
    # def line, and authors can't be expected to know which)
    DECORATED = (
        "import jax\n"
        "from functools import partial\n"
        "{before_dec}@partial(jax.jit, static_argnums=(2,)){on_dec}\n"
        "def step(params, opt_state, n):{on_def}\n"
        "    return params, opt_state\n")

    def test_suppression_on_decorator_line_covers_def_finding(self):
        out = lint(self.DECORATED.format(
            before_dec="",
            on_dec="  # zoolint: disable=DONATE004 — eval-only step",
            on_def=""))
        assert out == []

    def test_suppression_on_def_line_covers_decorator_finding(self):
        out = lint(self.DECORATED.format(
            before_dec="",
            on_dec="",
            on_def="  # zoolint: disable=DONATE004 — eval-only step"))
        assert out == []

    def test_suppression_above_decorator_covers_def_finding(self):
        out = lint(self.DECORATED.format(
            before_dec="# zoolint: disable=DONATE004 — eval-only\n",
            on_dec="", on_def=""))
        assert out == []

    def test_unsuppressed_decorated_def_still_fires(self):
        out = lint(self.DECORATED.format(
            before_dec="", on_dec="", on_def=""))
        assert rule_ids(out) == ["DONATE004"]


DIRTY = (
    "import jax\n"
    "@jax.jit\n"
    "def f(x):\n"
    "    print('hi')\n"
    "    return x\n")
DIRTY_TWICE = DIRTY + (
    "@jax.jit\n"
    "def g(x):\n"
    "    print('ho')\n"
    "    return x\n")


class TestBaseline:
    def test_baselined_findings_pass_and_shrink_is_enforced(self, tmp_path):
        baseline = tmp_path / "base.json"
        findings = lint(DIRTY_TWICE)
        assert len(findings) == 2
        write_baseline(str(baseline), findings)
        data = load_baseline(str(baseline))
        assert data["pre_fix_total"] == 2

        # unchanged code: everything covered, nothing stale
        new, stale = apply_baseline(lint(DIRTY_TWICE), data)
        assert new == [] and stale == []

        # one finding fixed: the baseline entry goes STALE — the run
        # must fail until the entry is removed (only-shrink)
        new, stale = apply_baseline(lint(DIRTY), data)
        assert new == []
        assert len(stale) == 1 and "no longer matched" in stale[0]

        # a novel finding is never absorbed by old entries
        novel = DIRTY_TWICE + (
            "@jax.jit\n"
            "def h(x):\n"
            "    print('new')\n"
            "    return x\n")
        new, stale = apply_baseline(lint(novel), data)
        assert len(new) == 1 and new[0].symbol == "h"

    def test_rewritten_baseline_keeps_pre_fix_total(self, tmp_path,
                                                    capsys):
        baseline = tmp_path / "base.json"
        src = tmp_path / "dirty.py"
        src.write_text(DIRTY_TWICE)
        assert zoolint_main(["--write-baseline", str(baseline),
                             str(src)]) == 0
        assert load_baseline(str(baseline))["pre_fix_total"] == 2
        # fix one, regenerate: total shrinks, pre_fix_total survives
        src.write_text(DIRTY)
        assert zoolint_main(["--write-baseline", str(baseline),
                             str(src)]) == 0
        data = load_baseline(str(baseline))
        assert data["total"] == 1 and data["pre_fix_total"] == 2


class TestDiff:
    def test_diff_reports_only_new_findings(self):
        old = lint(DIRTY)
        report = {"findings": [f.to_json() for f in old]}
        assert diff_findings(lint(DIRTY), report) == []
        new = diff_findings(lint(DIRTY_TWICE), report)
        assert len(new) == 1 and new[0].symbol == "g"


class TestCLIAndJson:
    def test_json_schema(self, tmp_path, capsys):
        src = tmp_path / "dirty.py"
        src.write_text(DIRTY)
        rc = zoolint_main(["--json", str(src)])
        assert rc == 1
        report = json.loads(capsys.readouterr().out)
        assert report["version"] == 1
        assert report["tool"] == "zoolint"
        assert report["total"] == 1
        assert report["counts"] == {"JIT001": 1}
        assert report["errors"] == []
        (f,) = report["findings"]
        assert set(f) == {"rule", "severity", "path", "line", "col",
                          "message", "symbol", "key"}
        assert f["rule"] == "JIT001" and f["severity"] == "error"
        assert f["line"] == 4 and f["symbol"] == "f"

    def test_exit_codes(self, tmp_path, capsys):
        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        assert zoolint_main([str(clean)]) == 0          # clean
        dirty = tmp_path / "dirty.py"
        dirty.write_text(DIRTY)
        assert zoolint_main([str(dirty)]) == 1          # findings
        assert zoolint_main([]) == 2                    # no paths
        assert zoolint_main(["--baseline", str(tmp_path / "nope.json"),
                             str(clean)]) == 2          # bad baseline
        capsys.readouterr()

    def test_unparseable_file_fails_loudly(self, tmp_path, capsys):
        bad = tmp_path / "bad.py"
        bad.write_text("def broken(:\n")
        assert zoolint_main([str(bad)]) == 1
        assert "syntax error" in capsys.readouterr().out

    def test_missing_path_fails_loudly(self, tmp_path, capsys):
        # a typo'd target must not silently shrink coverage
        assert zoolint_main([str(tmp_path / "no_such_dir")]) == 1
        assert "no such file" in capsys.readouterr().out

    def test_fresh_process_runs_the_graph_rule_families(self, tmp_path):
        # regression: rule registration must not depend on import
        # order — a fresh CLI process once silently skipped
        # SHARD007/MEM009 because the project link pass imported
        # rules.py first, and the registry guard then never imported
        # rules_graph
        (tmp_path / "bad.py").write_text(
            "from jax.sharding import PartitionSpec as P\n"
            "spec = P('bogus_axis')\n")
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO_ROOT, "scripts", "zoolint"),
             "--root", str(tmp_path), str(tmp_path)],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 1
        assert "SHARD007" in proc.stdout
        assert "bogus_axis" in proc.stdout

    def test_list_rules_names_all_families(self, capsys):
        assert zoolint_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in ("JIT001", "SYNC002", "COMPILE003", "DONATE004",
                    "RACE005", "RNG006", "SHARD007", "MEM009",
                    "COMPILE011",
                    # the v3 flow-sensitive families
                    "DONATE012", "ACK013", "RES015"):
            assert rid in out
        # LOCK010 is a project rule — the catalog must list it too
        assert "LOCK010" in out

    def test_help_epilog_generated_from_registry(self, capsys):
        """Regression (ISSUE 15 satellite): the --help epilog once
        described the PR 7 rule set long after new families shipped —
        it is now GENERATED from the registry, so every registered
        rule id must appear."""
        from analytics_zoo_tpu.analysis.cli import (build_parser,
                                                    rule_catalog)
        epilog = build_parser().epilog
        assert len(rule_catalog()) >= 13
        for rid, _sev, _doc in rule_catalog():
            assert rid in epilog, f"{rid} missing from --help epilog"


class TestJobsAndExplain:
    def _fixture_dir(self, tmp_path):
        (tmp_path / "dirty_a.py").write_text(DIRTY)
        (tmp_path / "dirty_b.py").write_text(
            DIRTY.replace("def f", "def g").replace("'hi'", "'ho'"))
        (tmp_path / "steps.py").write_text(
            "import jax\n"
            "def build():\n"
            "    def step(params, opt_state, batch):\n"
            "        return params, opt_state\n"
            "    return jax.jit(step)\n")
        # a flow-sensitive (CFG-based) finding too, so the --jobs
        # byte-identity test covers the v3 rule output as well
        (tmp_path / "res_leak.py").write_text(RES015_PROBE_LEAK)
        return tmp_path

    def test_jobs_output_identical_to_serial(self, tmp_path):
        # through scripts/zoolint (the jax-free loader) so the fork
        # pool REALLY runs — in-process (jax loaded) the pool refuses
        # to fork a multithreaded parent and degrades to serial
        d = self._fixture_dir(tmp_path)

        def run(*extra):
            return subprocess.run(
                [sys.executable,
                 os.path.join(REPO_ROOT, "scripts", "zoolint"),
                 *extra, "--root", str(d), str(d)],
                capture_output=True, text=True, timeout=120)

        serial = run()
        parallel = run("--jobs", "3")
        assert serial.returncode == parallel.returncode == 1
        assert serial.stdout == parallel.stdout
        assert "dirty_a.py" in serial.stdout
        assert "dirty_b.py" in serial.stdout

    def test_jobs_on_json_report_keeps_schema(self, tmp_path, capsys):
        d = self._fixture_dir(tmp_path)
        assert zoolint_main(["--jobs", "2", "--json", "--root",
                             str(d), str(d)]) == 1
        report = json.loads(capsys.readouterr().out)
        assert report["tool"] == "zoolint"
        assert report["total"] == len(report["findings"]) >= 3

    def test_explain_comms_prices_the_psum(self, tmp_path, capsys):
        d = self._fixture_dir(tmp_path)
        rc = zoolint_main(["--explain-comms", "--mesh", "data=8",
                           "--param-count", "1000", "--root", str(d),
                           str(d)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "steps.py" in out and "psum_grads" in out
        # 2(n-1)/n * 1000 params * 4 bytes, n=8 -> 7000
        assert "7,000 bytes/step" in out

    def test_explain_hbm_reports_donation_cost(self, tmp_path, capsys):
        d = self._fixture_dir(tmp_path)
        rc = zoolint_main(["--explain-hbm", "--param-bytes", "4000",
                           "--root", str(d), str(d)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "donated" in out and "not donated" in out


# ============================================== static↔runtime parity gate


class TestStaticCommParity:
    """ISSUE 7's acceptance criterion: SHARD007's static
    collective-bytes-per-step estimate must agree with PR 4's runtime
    ``collective_bytes_total`` identity to within ±10% on the tier-1
    allreduce trainer path (8-device data-parallel mesh)."""

    def test_static_estimate_matches_runtime_counters(self):
        import jax
        import numpy as np
        from analytics_zoo_tpu.analysis.comms import (
            estimate_train_step_comm_bytes)
        from analytics_zoo_tpu.common.config import get_config
        from analytics_zoo_tpu.common.triggers import MaxIteration
        from analytics_zoo_tpu.feature.feature_set import FeatureSet
        from analytics_zoo_tpu.observability import get_registry
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
        from analytics_zoo_tpu.pipeline.estimator import Estimator

        rs = np.random.RandomState(0)
        x = rs.randn(256, 8).astype(np.float32)
        y = rs.randn(256, 1).astype(np.float32)
        m = Sequential()
        m.add(Dense(4, input_shape=(8,)))
        m.add(Dense(1))
        m.compile(optimizer="sgd", loss="mse")

        reg = get_registry()
        c_bytes = reg.counter(
            "collective_bytes_total", "", labels=("op",)
        ).labels("psum_grads")
        c_steps = reg.counter(
            "collective_ops_total", "", labels=("op",)
        ).labels("psum_grads")
        bytes_before, steps_before = c_bytes.value, c_steps.value

        est = Estimator(m, optim_method=m.optim_method)
        # MaxIteration end-trigger forces the per-step engine (the
        # dispatch path that bumps the collective counters per step)
        est.train(FeatureSet.from_ndarrays(x, y), "mse",
                  end_trigger=MaxIteration(6), batch_size=64)

        steps = c_steps.value - steps_before
        assert steps >= 6
        runtime_per_step = (c_bytes.value - bytes_before) / steps

        params = m.get_variables()["params"]
        param_count = sum(int(np.prod(np.shape(leaf))) for leaf in
                          jax.tree_util.tree_leaves(params))
        mesh = est._mesh if est._mesh is not None else None
        dp = int(mesh.shape["data"]) if mesh is not None \
            else jax.device_count()
        fsdp = int(mesh.shape["fsdp"]) if mesh is not None else 1
        static = estimate_train_step_comm_bytes(
            param_count, dp, fsdp,
            str(get_config().get("train.grad_sync_dtype")))
        assert dp * fsdp == 8        # the tier-1 virtual pod
        assert static["psum_grads"] > 0
        assert abs(static["psum_grads"] - runtime_per_step) <= \
            0.10 * runtime_per_step, (
            f"static {static['psum_grads']} vs runtime "
            f"{runtime_per_step} bytes/step")


# ========================================================= the tier-1 gate


class TestRepoIsClean:
    """The acceptance gate: the shipped tree passes its own linter."""

    def test_full_pass_zero_nonbaselined_findings(self):
        """``scripts/zoolint analytics_zoo_tpu scripts examples``
        exits 0 against the checked-in baseline — and does so through
        the jax-free file-path loader (subprocess), exercising the
        --jobs process pool the CI stage uses.  Since ISSUE 15 this
        covers the flow-sensitive families too: zero non-baselined
        findings INCLUDING DONATE012/ACK013/RES015 (the empty
        baseline means every one their introduction surfaced was
        fixed, not acknowledged)."""
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                          "zoolint"),
             "--jobs", "4",
             "--baseline", BASELINE, "--root", REPO_ROOT,
             "analytics_zoo_tpu", "scripts", "examples"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, \
            f"zoolint found regressions:\n{proc.stdout}\n{proc.stderr}"

    def test_flow_families_run_in_a_fresh_gate_process(self):
        """The gate genuinely INCLUDES the v3 families: a fresh
        jax-free CLI process restricted to DONATE012/ACK013/RES015
        (a) lists them and (b) runs them over the real trainer /
        decode / serving donation+obligation sites clean — the
        acceptance's 'real sites stay clean while the seeded fixture
        fires' half (the fixture half lives in TestDONATE012 /
        TestHistoricalBugRegressions)."""
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                          "zoolint"), "--list-rules"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0
        for rid in ("DONATE012", "ACK013", "RES015"):
            assert rid in proc.stdout
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                          "zoolint"),
             "--rules", "DONATE012,ACK013,RES015",
             "--root", REPO_ROOT,
             "analytics_zoo_tpu", "scripts", "examples"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, \
            f"flow rules dirty:\n{proc.stdout}\n{proc.stderr}"

    def test_check_static_json_merged_report(self):
        """``check_static --json`` emits ONE machine-readable document
        folding zoolint's full report and metrics_lint's issues, so
        obs_report can later join static comm estimates against
        measured collective counters."""
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                          "check_static.py"),
             "--json", "--jobs", "2"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, \
            f"check_static --json failed:\n{proc.stdout[-2000:]}" \
            f"\n{proc.stderr[-2000:]}"
        doc = json.loads(proc.stdout)
        assert doc["tool"] == "check_static"
        assert doc["rc"] == 0
        assert doc["zoolint"]["tool"] == "zoolint"
        assert doc["zoolint"]["total"] == 0
        assert doc["metrics_lint"]["total"] == 0

    def test_check_static_json_metrics_args_counts(self, tmp_path):
        """Regression: the --metrics-args JSON branch once captured
        metrics_lint's trailing 'N issue(s)'/'clean' summary line as
        an issue — a clean dump reported issues=['clean'] and a dirty
        one overcounted total by one."""
        bad = tmp_path / "bad.txt"
        bad.write_text('# TYPE foo counter\n'
                       'foo{kind="a"} 1\n'
                       'foo{kind="a"} 2\n')
        clean = tmp_path / "clean.txt"
        clean.write_text('# TYPE foo_total counter\n'
                         'foo_total{kind="a"} 1\n')

        def run(dump):
            proc = subprocess.run(
                [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                              "check_static.py"),
                 "--json", "--skip-zoolint",
                 "--metrics-args", str(dump)],
                cwd=REPO_ROOT, capture_output=True, text=True,
                timeout=120)
            return proc.returncode, \
                json.loads(proc.stdout)["metrics_lint"]

        rc, ml = run(bad)
        assert rc == 1
        assert ml["total"] == len(ml["issues"]) == 2
        assert not any("issue(s)" in i for i in ml["issues"])
        rc, ml = run(clean)
        assert rc == 0
        assert ml == {"total": 0, "issues": []}

    def test_baseline_strictly_below_pre_fix_count(self):
        data = load_baseline(BASELINE)
        assert data["total"] < data["pre_fix_total"], (
            "the baseline may only shrink: fix findings, don't "
            "re-baseline them")

    def test_check_static_entry_point(self):
        """The folded entry point (zoolint + metrics_lint) is the one
        CI hook; it must stay green and jax-free."""
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                          "check_static.py")],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, \
            f"check_static failed:\n{proc.stdout}\n{proc.stderr}"
        assert "zoolint" in proc.stdout
        assert "metrics_lint" in proc.stdout


# ========================================== zoolint v3: CFG + typestate


# the PR 9 breaker half-open probe-slot leak, distilled: the command-
# error handler re-raises WITHOUT releasing the probe slot the
# preceding allow() claimed — the breaker wedges HALF_OPEN forever
# while /healthz (watching only OPEN) reads ready
RES015_PROBE_LEAK = (
    "class BreakerClient:\n"
    "    def _call(self, name):\n"
    "        if not self.breaker.allow():\n"
    "            raise ConnectionError('open')\n"
    "        try:\n"
    "            out = self._do(name)\n"
    "        except RuntimeError:\n"
    "            raise\n"
    "        self.breaker.record_success()\n"
    "        return out\n")

# the fixed shape PR 9 shipped: every outcome — command error
# included — records before propagating
RES015_PROBE_FIXED = RES015_PROBE_LEAK.replace(
    "        except RuntimeError:\n            raise\n",
    "        except RuntimeError:\n"
    "            self.breaker.record_success()\n"
    "            raise\n")

# the PR 13 reclaim double-judge, distilled to its path shape: one
# iteration can BOTH quarantine a record (error result + ack) AND
# serve it — the client-visible defect was exactly a record settled
# twice, the second settlement overwriting a delivered result with an
# error (7 innocent records in the first storm run)
ACK013_DOUBLE_JUDGE = (
    "class Reclaimer:\n"
    "    def reclaim(self):\n"
    "        entries = self.broker.xautoclaim('s', 'g', 'me', 1000)\n"
    "        for entry_id, fields in entries:\n"
    "            attempts = int(self.counts.get(str(entry_id), 0))\n"
    "            if attempts + 1 >= self.max_attempts:\n"
    "                self._quarantine(entry_id, fields)\n"
    "            self._serve_entries([(entry_id, fields)])\n")

# the fixed shape (server._reclaim_stale today): the already-served
# guard finishes the lost ack and every branch settles exactly once
ACK013_RECLAIM_FIXED = (
    "class Reclaimer:\n"
    "    def reclaim(self):\n"
    "        entries = self.broker.xautoclaim('s', 'g', 'me', 1000)\n"
    "        entries = [e for e in entries\n"
    "                   if e[0] not in self._inflight]\n"
    "        for entry_id, fields in entries:\n"
    "            key = self._rid_of(fields) or str(entry_id)\n"
    "            if self._reclaim_already_served(entry_id, fields,\n"
    "                                            key):\n"
    "                continue\n"
    "            attempts = int(self.counts.get(key, 0))\n"
    "            if attempts + 1 >= self.max_attempts:\n"
    "                self._quarantine(entry_id, fields)\n"
    "                continue\n"
    "            self._serve_entries([(entry_id, fields)])\n")

SERVING_PATH = "analytics_zoo_tpu/serving/snippet.py"


def serving_lint(src, rules=None):
    return analyze_source(src, path=SERVING_PATH, rule_ids=rules)


class TestCFG:
    """The CFG builder's edge sets, asserted EXACTLY — these are the
    structures the typestate rules' correctness rests on."""

    @staticmethod
    def edges(src):
        import ast as _ast
        from analytics_zoo_tpu.analysis.cfg import build_cfg
        fn = _ast.parse(src).body[0]
        return set(build_cfg(fn).edges())

    def test_try_finally_with_return_inside(self):
        got = self.edges(
            "def f(x):\n"
            "    try:\n"                    # 2
            "        return work(x)\n"      # 3
            "    finally:\n"
            "        cleanup()\n")          # 5
        assert got == {
            "entry ->next Return@3",
            # the return's value can raise -> exc copy of the finally
            "Return@3 ->exc Expr@5#2",
            # normal return unwinds through its own finally copy
            "Return@3 ->next Expr@5",
            "Expr@5 ->next exit",
            "Expr@5 ->exc raise",
            "Expr@5#2 ->next raise",
            "Expr@5#2 ->exc raise",
        }

    def test_try_finally_with_break_and_continue_inside(self):
        got = self.edges(
            "def f(xs):\n"
            "    for x in xs:\n"            # 2
            "        try:\n"                # 3
            "            if bad(x):\n"      # 4
            "                break\n"       # 5
            "            continue\n"        # 6
            "        finally:\n"
            "            cleanup()\n"       # 8
            "    return 1\n")               # 9
        assert got == {
            "entry ->next For@2",
            "For@2 ->true If@4",
            "For@2 ->false Return@9",
            "If@4 ->true Break@5",
            "If@4 ->false Continue@6",
            "If@4 ->exc Expr@8#3",          # test can raise
            # continue unwinds through ITS finally copy, back to the
            # loop header
            "Continue@6 ->next Expr@8",
            "Expr@8 ->next For@2",
            "Expr@8 ->exc raise",
            # break unwinds through a DIFFERENT copy, then PAST the
            # loop (skipping any else) to the statement after it
            "Break@5 ->next Expr@8#2",
            "Expr@8#2 ->next Return@9",
            "Expr@8#2 ->exc raise",
            # the exception copy re-raises after cleanup
            "Expr@8#3 ->next raise",
            "Expr@8#3 ->exc raise",
            "Return@9 ->next exit",
        }

    def test_with_and_exception_edges(self):
        got = self.edges(
            "def f(x):\n"
            "    with open(x) as fh:\n"     # 2
            "        work(fh)\n"            # 3
            "    return fh\n")              # 4
        assert got == {
            "entry ->next With@2",
            "With@2 ->next Expr@3",
            "With@2 ->exc raise",           # context entry can raise
            "Expr@3 ->next Return@4",
            "Expr@3 ->exc raise",           # body escapes uncaught
            "Return@4 ->next exit",
        }

    def test_for_else_and_break_skips_else(self):
        got = self.edges(
            "def f(xs):\n"
            "    for x in xs:\n"            # 2
            "        if probe(x):\n"        # 3
            "            break\n"           # 4
            "    else:\n"
            "        exhausted()\n"         # 6
            "    tail()\n")                 # 7
        assert got == {
            "entry ->next For@2",
            "For@2 ->true If@3",
            "For@2 ->false Expr@6",         # exhaustion runs else
            "If@3 ->true Break@4",
            "If@3 ->false For@2",
            "If@3 ->exc raise",
            "Break@4 ->next Expr@7",        # break SKIPS else
            "Expr@6 ->next Expr@7",
            "Expr@6 ->exc raise",
            "Expr@7 ->next exit",
            "Expr@7 ->exc raise",
        }

    def test_while_else(self):
        got = self.edges(
            "def f(n):\n"
            "    while n:\n"                # 2
            "        n = step(n)\n"         # 3
            "    else:\n"
            "        done()\n"              # 5
            "    return n\n")               # 6
        assert got == {
            "entry ->next While@2",
            "While@2 ->true Assign@3",
            "While@2 ->false Expr@5",
            "Assign@3 ->next While@2",
            "Assign@3 ->exc raise",
            "Expr@5 ->next Return@6",
            "Expr@5 ->exc raise",
            "Return@6 ->next exit",
        }

    def test_nested_handlers_and_bare_raise(self):
        got = self.edges(
            "def f(x):\n"
            "    try:\n"                    # 2
            "        try:\n"                # 3
            "            op(x)\n"           # 4
            "        except KeyError:\n"    # 5
            "            raise\n"           # 6
            "    except Exception:\n"       # 7
            "        handle()\n")           # 8
        assert got == {
            "entry ->next Expr@4",
            "Expr@4 ->exc ExceptHandler@5",
            "Expr@4 ->next exit",
            "ExceptHandler@5 ->next Raise@6",
            # the bare re-raise propagates to the OUTER handler
            "Raise@6 ->exc ExceptHandler@7",
            "ExceptHandler@7 ->next Expr@8",
            "Expr@8 ->next exit",
            "Expr@8 ->exc raise",
        }

    def test_exception_edge_goes_to_every_handler(self):
        got = self.edges(
            "def f(x):\n"
            "    try:\n"                    # 2
            "        op(x)\n"               # 3
            "    except KeyError:\n"        # 4
            "        a()\n"                 # 5
            "    except ValueError:\n"      # 6
            "        b()\n")                # 7
        assert "Expr@3 ->exc ExceptHandler@4" in got
        assert "Expr@3 ->exc ExceptHandler@6" in got
        # no direct escape: handlers absorb (re-raise is explicit)
        assert "Expr@3 ->exc raise" not in got

    def test_run_forward_reaches_fixpoint_on_loops(self):
        import ast as _ast
        from analytics_zoo_tpu.analysis.cfg import (build_cfg,
                                                    run_forward)
        fn = _ast.parse(
            "def f(xs):\n"
            "    for x in xs:\n"
            "        y = x\n"
            "    return y\n").body[0]
        cfg = build_cfg(fn)
        seen = []

        def transfer(node, state):
            seen.append(node.label())
            out = dict(state)
            if node.label() == "Assign@3":
                out["y"] = frozenset({"set"})
            return {None: out}

        states = run_forward(cfg, {}, transfer)
        assert states[cfg.exit].get("y") == frozenset({"set"})


class TestDONATE012:
    STEP_CORE_PATTERN = (
        "from analytics_zoo_tpu.compile import engine_jit\n"
        "class T:\n"
        "    def _step_core(self, params, opt_state, state, batch,\n"
        "                   rng):\n"
        "        return params, opt_state, state, 0.0\n"
        "    def build(self):\n"
        "        self._train_step = engine_jit(\n"
        "            self._step_core, donate_argnums=(0, 1, 2))\n"
        "    def run(self, params, opt_state, state, batches, rng):\n"
        "        for b in batches:\n"
        "            {call}\n"
        "            {after}\n")

    def test_seeded_step_core_use_after_donate_is_caught(self):
        """ISSUE 15 acceptance: a copy of trainer._step_core's calling
        pattern with the donated params read after the call."""
        src = self.STEP_CORE_PATTERN.format(
            call="new_p, new_o, new_s, loss = self._train_step(\n"
                 "                params, opt_state, state, b, rng)",
            after="record(loss, params)")
        out = lint(src, rules=["DONATE012"])
        assert out and all(f.rule == "DONATE012" and
                           f.severity == "error" for f in out)
        assert any("'params'" in f.message for f in out)

    def test_rebinding_rearms(self):
        src = self.STEP_CORE_PATTERN.format(
            call="params, opt_state, state, loss = self._train_step(\n"
                 "                params, opt_state, state, b, rng)",
            after="record(loss, params)")
        assert lint(src, rules=["DONATE012"]) == []

    def test_exception_edge_read_fires_and_handler_rebind_is_clean(self):
        tmpl = (
            "from analytics_zoo_tpu.compile import engine_jit\n"
            "class P:\n"
            "    def __init__(self, fn):\n"
            "        self._step = engine_jit(fn, donate_argnums=(1, 2))\n"
            "    def admit(self, ids):\n"
            "        try:\n"
            "            self._tokens, self._carries = self._step(\n"
            "                self._params, self._tokens,\n"
            "                self._carries, ids)\n"
            "        except Exception:\n"
            "            {handler}\n"
            "            raise\n")
        # the decode.py discipline: the handler REBUILDS before any
        # read — the donated buffers may be gone even though the call
        # raised
        clean = tmpl.format(
            handler="self._tokens, self._carries = self._fresh()")
        assert lint(clean, rules=["DONATE012"]) == []
        dirty = tmpl.format(handler="log(self._tokens)")
        out = lint(dirty, rules=["DONATE012"])
        assert [f.rule for f in out] == ["DONATE012"]
        assert "'self._tokens'" in out[0].message

    def test_warm_is_exempt(self):
        src = (
            "from analytics_zoo_tpu.compile import engine_jit\n"
            "class P:\n"
            "    def __init__(self, fn):\n"
            "        self._step = engine_jit(fn, donate_argnums=(0,))\n"
            "    def warm(self, state, ids):\n"
            "        self._step.warm(state, ids)\n"
            "        return state.shape\n")
        assert lint(src, rules=["DONATE012"]) == []

    def test_nonliteral_donate_positions_exempt(self):
        src = (
            "from analytics_zoo_tpu.compile import engine_jit\n"
            "def build(fn, donate):\n"
            "    step = engine_jit(fn, donate_argnums=donate)\n"
            "    def run(state, b):\n"
            "        out = step(state, b)\n"
            "        return out, state\n"
            "    return run\n")
        assert lint(src, rules=["DONATE012"]) == []

    def test_cross_module_donation_via_project_facts(self, tmp_path):
        from analytics_zoo_tpu.analysis import analyze_paths
        (tmp_path / "prog.py").write_text(
            "from analytics_zoo_tpu.compile import engine_jit\n"
            "def _f(state, b):\n"
            "    return state\n"
            "step = engine_jit(_f, donate_argnums=(0,))\n")
        (tmp_path / "driver.py").write_text(
            "from prog import step\n"
            "def run(state, batches):\n"
            "    for b in batches:\n"
            "        out = step(state, b)\n"
            "    return state\n")
        findings, errors = analyze_paths(
            [str(tmp_path)], root=str(tmp_path),
            rule_ids=["DONATE012"])
        assert errors == []
        assert [f.rule for f in findings] and \
            all(f.path == "driver.py" for f in findings)
        assert any("'state'" in f.message for f in findings)


class TestACK013:
    def test_scoped_to_serving(self):
        # the same source outside serving/ is out of scope
        assert lint(ACK013_DOUBLE_JUDGE, rules=["ACK013"]) == []

    def test_record_leak_on_swallowed_exception_path(self):
        src = (
            "class W:\n"
            "    def drain(self):\n"
            "        entries = self.broker.xreadgroup('g', 'me', 's')\n"
            "        for entry_id, fields in entries:\n"
            "            try:\n"
            "                self._serve_entries([(entry_id, fields)])\n"
            "            except Exception:\n"
            "                continue\n")
        out = serving_lint(src, rules=["ACK013"])
        assert [f.rule for f in out] == ["ACK013"]
        assert "pending forever" in out[0].message

    def test_reraise_to_loop_boundary_is_a_valid_discharge(self):
        # the PEL-reclaim contract: dying un-acked is deliberate
        src = (
            "class W:\n"
            "    def drain(self):\n"
            "        entries = self.broker.xreadgroup('g', 'me', 's')\n"
            "        for entry_id, fields in entries:\n"
            "            self._serve_entries([(entry_id, fields)])\n")
        assert serving_lint(src, rules=["ACK013"]) == []

    def test_dead_letter_in_handler_is_clean(self):
        src = (
            "class W:\n"
            "    def drain(self):\n"
            "        entries = self.broker.xreadgroup('g', 'me', 's')\n"
            "        for entry_id, fields in entries:\n"
            "            try:\n"
            "                self._serve_entries([(entry_id, fields)])\n"
            "            except Exception:\n"
            "                self.dead_letter(entry_id)\n")
        assert serving_lint(src, rules=["ACK013"]) == []

    def test_request_leak_on_early_return(self):
        src = (
            "from analytics_zoo_tpu.serving.engine.batcher import "
            "Request\n"
            "def handle(engine, data, cond):\n"
            "    req = Request(endpoint='e', uri='', data=data)\n"
            "    if cond:\n"
            "        return None\n"
            "    engine.submit_wait([req])\n"
            "    return req.result\n")
        out = serving_lint(src, rules=["ACK013"])
        assert [f.rule for f in out] == ["ACK013"]
        assert "blocks until the transport timeout" in out[0].message

    def test_request_fail_on_every_path_is_clean(self):
        src = (
            "from analytics_zoo_tpu.serving.engine.batcher import "
            "Request\n"
            "def handle(engine, data, cond):\n"
            "    req = Request(endpoint='e', uri='', data=data)\n"
            "    if cond:\n"
            "        req.fail(ValueError('shed'))\n"
            "        return None\n"
            "    engine.submit_wait([req])\n"
            "    return req.result\n")
        assert serving_lint(src, rules=["ACK013"]) == []

    def test_request_double_discharge_and_done_guard(self):
        dbl = (
            "from analytics_zoo_tpu.serving.engine.batcher import "
            "Request\n"
            "def handle(engine, data, cond):\n"
            "    req = Request(endpoint='e', uri='', data=data)\n"
            "    req.fail(ValueError('a'))\n"
            "    if cond:\n"
            "        req.fail(ValueError('b'))\n"
            "    return req\n")
        out = serving_lint(dbl, rules=["ACK013"])
        assert [f.rule for f in out] == ["ACK013"]
        assert "second discharge" in out[0].message
        guarded = dbl.replace("if cond:", "if not req.done:")
        assert serving_lint(guarded, rules=["ACK013"]) == []

    def test_inspection_self_call_with_id_only_is_not_a_discharge(
            self):
        """Regression: a logging/metrics helper taking only the entry
        ID is an inspection — counting it as an ownership transfer
        minted a spurious double-settle on the real serve that
        followed.  Settling needs the record's PAYLOAD: transfers to
        self-methods require the fields var too (the ack vocabulary
        keeps working by id alone — acks go by entry id)."""
        src = (
            "class W:\n"
            "    def drain(self):\n"
            "        entries = self.broker.xreadgroup('g', 'me', 's')\n"
            "        for entry_id, fields in entries:\n"
            "            self._log_claim(entry_id)\n"
            "            self._serve_entries([(entry_id, fields)])\n")
        assert serving_lint(src, rules=["ACK013"]) == []

    def test_request_escape_via_container_store_is_clean(self):
        src = (
            "from analytics_zoo_tpu.serving.engine.batcher import "
            "Request\n"
            "def enqueue(pending, data):\n"
            "    req = Request(endpoint='e', uri='', data=data)\n"
            "    pending.append((0.0, req))\n")
        assert serving_lint(src, rules=["ACK013"]) == []


BATCHJOBS_PATH = "analytics_zoo_tpu/batchjobs/snippet.py"

# a leased shard swallowed on the error path: leased-but-never-settled,
# invisible to peers until the lease times out
ACK013_SHARD_LEAK = (
    "class W:\n"
    "    def run(self):\n"
    "        shards = self.lease.claim_shards(limit=1)\n"
    "        for shard_id, shard in shards:\n"
    "            try:\n"
    "                self._commit_shard(shard_id, shard)\n"
    "            except Exception:\n"
    "                continue\n")


def batchjobs_lint(src, rules=None):
    return analyze_source(src, path=BATCHJOBS_PATH, rule_ids=rules)


class TestACK013Batchjobs:
    """ISSUE 17 satellite: the exactly-once obligation now guards the
    batchjobs shard ledger too — same rule, second scope."""

    def test_shard_leak_fires_in_batchjobs_scope(self):
        out = batchjobs_lint(ACK013_SHARD_LEAK, rules=["ACK013"])
        assert [f.rule for f in out] == ["ACK013"]
        assert "pending forever" in out[0].message

    def test_same_source_out_of_both_scopes_is_clean(self):
        assert analyze_source(
            ACK013_SHARD_LEAK,
            path="analytics_zoo_tpu/data/snippet.py",
            rule_ids=["ACK013"]) == []

    def test_serving_scope_still_checked(self):
        # the scope extension must not narrow the original scope
        out = serving_lint(ACK013_DOUBLE_JUDGE, rules=["ACK013"])
        assert [f.rule for f in out] == ["ACK013"]

    def test_release_in_handler_is_clean(self):
        src = (
            "class W:\n"
            "    def run(self):\n"
            "        shards = self.lease.claim_shards(limit=1)\n"
            "        for shard_id, shard in shards:\n"
            "            try:\n"
            "                self._commit_shard(shard_id, shard)\n"
            "            except Exception:\n"
            "                self.lease.release_shard(shard_id)\n")
        assert batchjobs_lint(src, rules=["ACK013"]) == []

    def test_raise_to_loop_boundary_is_a_valid_discharge(self):
        # lease-lapse contract: dying un-settled hands the shard to a
        # replacement via lease expiry — the batch twin of PEL reclaim
        src = (
            "class W:\n"
            "    def run(self):\n"
            "        shards = self.lease.claim_shards(limit=1)\n"
            "        for shard_id, shard in shards:\n"
            "            self._commit_shard(shard_id, shard)\n")
        assert batchjobs_lint(src, rules=["ACK013"]) == []

    def test_double_settle_commit_then_release_fires(self):
        src = (
            "class W:\n"
            "    def run(self):\n"
            "        shards = self.lease.claim_shards(limit=1)\n"
            "        for shard_id, shard in shards:\n"
            "            self._commit_shard(shard_id, shard)\n"
            "            self.lease.release_shard(shard_id)\n")
        out = batchjobs_lint(src, rules=["ACK013"])
        assert [f.rule for f in out] == ["ACK013"]
        assert "double-settles" in out[0].message

    def test_real_worker_loop_is_clean(self):
        # the SHIPPED claim→score→commit loop must satisfy its own
        # lint (the static gate runs it, but assert it directly so a
        # refactor can't silently fall out of scope)
        path = os.path.join(REPO_ROOT, "analytics_zoo_tpu",
                            "batchjobs", "worker.py")
        with open(path, encoding="utf-8") as f:
            src = f.read()
        assert analyze_source(
            src, path="analytics_zoo_tpu/batchjobs/worker.py",
            rule_ids=["ACK013"]) == []


class TestRES015:
    def test_manual_acquire_without_release_on_exception_path(self):
        src = (
            "def work(q, state_lock):\n"
            "    state_lock.acquire()\n"
            "    item = q.get_nowait()\n"
            "    state_lock.release()\n"
            "    return item\n")
        out = lint(src, rules=["RES015"])
        assert [f.rule for f in out] == ["RES015"]
        fixed = (
            "def work(q, state_lock):\n"
            "    state_lock.acquire()\n"
            "    try:\n"
            "        item = q.get_nowait()\n"
            "    finally:\n"
            "        state_lock.release()\n"
            "    return item\n")
        assert lint(fixed, rules=["RES015"]) == []

    def test_with_based_locking_is_not_this_rules_business(self):
        src = (
            "def work(q, state_lock):\n"
            "    with state_lock:\n"
            "        return q.get_nowait()\n")
        assert lint(src, rules=["RES015"]) == []

    def test_nondaemon_thread_join_paths(self):
        leak = (
            "import threading\n"
            "def run(producer, drain):\n"
            "    t = threading.Thread(target=producer)\n"
            "    t.start()\n"
            "    drain()\n"
            "    t.join()\n")
        out = lint(leak, rules=["RES015"])
        assert [f.rule for f in out] == ["RES015"]
        fixed = leak.replace(
            "    drain()\n    t.join()\n",
            "    try:\n        drain()\n    finally:\n"
            "        t.join()\n")
        assert lint(fixed, rules=["RES015"]) == []
        daemon = leak.replace("target=producer",
                              "target=producer, daemon=True")
        assert lint(daemon, rules=["RES015"]) == []

    def test_assigned_guard_refines_acquisition(self):
        """Regression: ``ok = breaker.allow(); if not ok: return``
        acquires nothing on the falsy arm — the bound guard variable
        must refine the obligation like the bare in-test call form
        does."""
        src = (
            "class C:\n"
            "    def call(self):\n"
            "        ok = self.breaker.allow()\n"
            "        if not ok:\n"
            "            return None\n"
            "        out = self._do()\n"
            "        self.breaker.record_success()\n"
            "        return out\n")
        out = lint(src, rules=["RES015"])
        # the remaining finding would be the _do() exception path —
        # which IS a real leak; silence it with a try/except to prove
        # the guard itself is clean
        assert [f.rule for f in out] == ["RES015"]
        guarded = src.replace(
            "        out = self._do()\n",
            "        try:\n"
            "            out = self._do()\n"
            "        except Exception:\n"
            "            self.breaker.record_failure()\n"
            "            raise\n")
        assert lint(guarded, rules=["RES015"]) == []
        lock = (
            "def work(q, state_lock):\n"
            "    got = state_lock.acquire(False)\n"
            "    if not got:\n"
            "        return None\n"
            "    item = None\n"
            "    state_lock.release()\n"
            "    return item\n")
        assert lint(lock, rules=["RES015"]) == []

    def test_daemon_attribute_form_is_exempt(self):
        """Regression: ``t.daemon = True`` daemonizes like the
        constructor keyword — the attribute form was flagged as an
        unjoined non-daemon thread."""
        src = (
            "import threading\n"
            "def run(producer, drain):\n"
            "    t = threading.Thread(target=producer)\n"
            "    t.daemon = True\n"
            "    t.start()\n"
            "    drain()\n")
        assert lint(src, rules=["RES015"]) == []

    def test_popen_escape_vs_leak(self):
        leak = (
            "import subprocess, sys\n"
            "def start(script, check):\n"
            "    proc = subprocess.Popen([sys.executable, script])\n"
            "    check(script)\n")
        out = lint(leak, rules=["RES015"])
        assert [f.rule for f in out] == ["RES015"]
        # the launcher pattern: handing the proc to a monitor is the
        # discharge (the monitor owns reaping from then on)
        escaped = leak.replace(
            "    check(script)\n",
            "    monitor.register(proc)\n")
        assert lint(escaped, rules=["RES015"]) == []
        waited = leak.replace(
            "    check(script)\n",
            "    try:\n        check(script)\n    finally:\n"
            "        proc.wait()\n")
        assert lint(waited, rules=["RES015"]) == []


class TestHistoricalBugRegressions:
    """ISSUE 15 acceptance: the two historical runtime-caught bugs are
    re-detected STATICALLY — each as a positive fixture plus the
    fixed-code negative."""

    def test_pr9_breaker_probe_slot_leak_detected(self):
        out = lint(RES015_PROBE_LEAK, rules=["RES015"])
        assert [f.rule for f in out] == ["RES015"]
        assert "probe slot" in out[0].message
        assert "HALF_OPEN" in out[0].message

    def test_pr9_fixed_code_is_clean(self):
        assert lint(RES015_PROBE_FIXED, rules=["RES015"]) == []

    def test_pr13_reclaim_double_judge_detected(self):
        out = serving_lint(ACK013_DOUBLE_JUDGE, rules=["ACK013"])
        assert [f.rule for f in out] == ["ACK013"]
        assert "PR 13" in out[0].message

    def test_pr13_fixed_code_is_clean(self):
        assert serving_lint(ACK013_RECLAIM_FIXED,
                            rules=["ACK013"]) == []

    def test_real_breaker_and_reclaim_sites_are_clean(self):
        """The shipped redis_client/server code (which contains the
        FIXES) passes the rules that would have caught the bugs."""
        from analytics_zoo_tpu.analysis import analyze_paths
        findings, errors = analyze_paths(
            [os.path.join(REPO_ROOT, "analytics_zoo_tpu", "serving")],
            root=REPO_ROOT, rule_ids=["ACK013", "RES015", "DONATE012"])
        assert errors == []
        assert findings == [], [f.render() for f in findings]


# ======================== RACE016 / ATOM017 / PUBLISH018 / WRITE019


RACE016_CROSS_ROLE = (
    "import threading\n"
    "\n"
    "class BacklogDrain:\n"
    "    def __init__(self):\n"
    "        self.pending = []\n"
    "        self._thread = None\n"
    "\n"
    "    def start(self):\n"
    "        self._thread = threading.Thread(\n"
    "            target=self._loop, name='zoo-drain-loop')\n"
    "        self._thread.start()\n"
    "\n"
    "    def _loop(self):\n"
    "        while self.pending:\n"
    "            self.pending.pop()\n"
    "\n"
    "    def submit(self, item):\n"
    "        self.pending.append(item)\n")

#: the Queue-handoff version of the same pipeline: the sync-typed
#: attribute carries its own ordering contract
RACE016_QUEUE_HANDOFF = RACE016_CROSS_ROLE.replace(
    "import threading\n",
    "import queue\nimport threading\n").replace(
    "        self.pending = []\n",
    "        self.pending = queue.Queue()\n").replace(
    "        while self.pending:\n"
    "            self.pending.pop()\n",
    "        while True:\n"
    "            self.pending.get()\n").replace(
    "        self.pending.append(item)\n",
    "        self.pending.put(item)\n")

RACE016_SAME_LOCK = (
    "import threading\n"
    "\n"
    "class BacklogDrain:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self.pending = []\n"
    "        self._thread = None\n"
    "\n"
    "    def start(self):\n"
    "        self._thread = threading.Thread(\n"
    "            target=self._loop, name='zoo-drain-loop')\n"
    "        self._thread.start()\n"
    "\n"
    "    def _loop(self):\n"
    "        with self._lock:\n"
    "            while self.pending:\n"
    "                self.pending.pop()\n"
    "\n"
    "    def submit(self, item):\n"
    "        with self._lock:\n"
    "            self.pending.append(item)\n")

RACE016_PRESTART_INIT = (
    "import threading\n"
    "\n"
    "class Warmup:\n"
    "    def __init__(self):\n"
    "        self.table = {}\n"
    "        self._thread = None\n"
    "\n"
    "    def start(self):\n"
    "        self.table['seed'] = 1\n"
    "        self.table.update({'a': 2})\n"
    "        self._thread = threading.Thread(\n"
    "            target=self._loop, name='zoo-warm-loop')\n"
    "        self._thread.start()\n"
    "\n"
    "    def _loop(self):\n"
    "        while True:\n"
    "            _ = self.table.get('seed')\n")

RACE016_MONOTONIC_FLAG = (
    "import threading\n"
    "\n"
    "class Loop:\n"
    "    def __init__(self):\n"
    "        self._stop = False\n"
    "        self._thread = None\n"
    "\n"
    "    def start(self):\n"
    "        self._thread = threading.Thread(\n"
    "            target=self._loop, name='zoo-loop')\n"
    "        self._thread.start()\n"
    "\n"
    "    def _loop(self):\n"
    "        while not self._stop:\n"
    "            pass\n"
    "\n"
    "    def close(self):\n"
    "        self._stop = True\n")


class TestRACE016:
    def test_cross_role_mutation_fires(self):
        out = lint(RACE016_CROSS_ROLE, rules=["RACE016"])
        assert rule_ids(out) == ["RACE016"]
        f = out[0]
        assert f.severity == "error"
        assert f.symbol == "BacklogDrain.pending"
        assert "role" in f.message
        assert "zoo-racecheck" in f.message    # the runtime twin

    def test_queue_handoff_is_clean(self):
        assert lint(RACE016_QUEUE_HANDOFF, rules=["RACE016"]) == []

    def test_same_lock_both_sides_is_clean(self):
        assert lint(RACE016_SAME_LOCK, rules=["RACE016"]) == []

    def test_prestart_initialization_is_clean(self):
        """Writes in __init__ AND in start() before the spawn are
        construction: nothing else can hold the instance yet."""
        assert lint(RACE016_PRESTART_INIT, rules=["RACE016"]) == []

    def test_monotonic_flag_publication_is_clean(self):
        """Plain constant write on one role / read on another is the
        sanctioned GIL-atomic stop-flag idiom."""
        assert lint(RACE016_MONOTONIC_FLAG, rules=["RACE016"]) == []


ATOM017_BACKLOG_SEEN = (
    "import threading\n"
    "\n"
    "class GaugeRegistry:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self._backlog_seen = {}\n"
    "\n"
    "    def observe(self, key, gauge):\n"
    "        if key not in self._backlog_seen:\n"
    "            with self._lock:\n"
    "                self._backlog_seen[key] = gauge\n")

ATOM017_BACKLOG_FIXED = (
    "import threading\n"
    "\n"
    "class GaugeRegistry:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self._backlog_seen = {}\n"
    "\n"
    "    def observe(self, key, gauge):\n"
    "        with self._lock:\n"
    "            if key not in self._backlog_seen:\n"
    "                self._backlog_seen[key] = gauge\n")

ATOM017_DOUBLE_CHECKED = (
    "import threading\n"
    "\n"
    "class GaugeRegistry:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self._backlog_seen = {}\n"
    "\n"
    "    def observe(self, key, gauge):\n"
    "        if key not in self._backlog_seen:\n"
    "            with self._lock:\n"
    "                if key not in self._backlog_seen:\n"
    "                    self._backlog_seen[key] = gauge\n")


class TestATOM017:
    def test_backlog_seen_shape_fires(self):
        """The PR 12 registry-gauge stomping: guard reads the dict
        with no lock, the store runs under the lock — two samplers
        both pass the check, the second stomps the first's gauge."""
        out = lint(ATOM017_BACKLOG_SEEN, rules=["ATOM017"])
        assert rule_ids(out) == ["ATOM017"]
        assert out[0].severity == "error"
        assert "_backlog_seen" in out[0].message

    def test_guard_under_the_same_lock_is_clean(self):
        assert lint(ATOM017_BACKLOG_FIXED, rules=["ATOM017"]) == []

    def test_double_checked_locking_is_clean(self):
        """The re-check under the write's lock kills the stale outer
        guard — sanctioned double-checked locking."""
        assert lint(ATOM017_DOUBLE_CHECKED, rules=["ATOM017"]) == []


PUBLISH018_LATE_PID = (
    "import threading\n"
    "\n"
    "class Replica:\n"
    "    def spawn(self):\n"
    "        t = threading.Thread(target=self._watch)\n"
    "        t.start()\n"
    "        self.pid = 4242\n"
    "\n"
    "    def _watch(self):\n"
    "        return self.pid\n")

PUBLISH018_INIT_FIRST = (
    "import threading\n"
    "\n"
    "class Replica:\n"
    "    def spawn(self):\n"
    "        self.pid = 4242\n"
    "        t = threading.Thread(target=self._watch)\n"
    "        t.start()\n"
    "\n"
    "    def _watch(self):\n"
    "        return self.pid\n")


class TestPUBLISH018:
    def test_mutation_after_start_fires(self):
        """The flight-recorder replica.spawn ordering incident: the
        watch loop read a replica record before its pid field
        landed.  Regression for the state-machine walk order too —
        the non-chained construct-then-start form must publish."""
        out = lint(PUBLISH018_LATE_PID, rules=["PUBLISH018"])
        assert rule_ids(out) == ["PUBLISH018"]
        assert out[0].severity == "warning"
        assert "self.pid" in out[0].message
        assert "unsafe publication" in out[0].message

    def test_untouched_attr_mutation_is_not_flagged(self):
        """Only attrs the spawn target actually touches can be
        observed half-built; others belong to RACE016."""
        src = PUBLISH018_LATE_PID.replace("self.pid = 4242",
                                          "self.other = 4242")
        assert lint(src, rules=["PUBLISH018"]) == []

    def test_init_before_start_is_clean(self):
        assert lint(PUBLISH018_INIT_FIRST, rules=["PUBLISH018"]) == []


WRITE019_TORN = (
    "import json\n"
    "\n"
    "def write_progress(run_dir, doc):\n"
    "    with open(run_dir + '/progress.json', 'w') as f:\n"
    "        json.dump(doc, f)\n")

WRITE019_ATOMIC = (
    "import json\n"
    "from analytics_zoo_tpu.common.fsutil import atomic_write_text\n"
    "\n"
    "def write_progress(run_dir, doc):\n"
    "    atomic_write_text(run_dir + '/progress.json',\n"
    "                      json.dumps(doc))\n")


class TestWRITE019:
    def test_non_atomic_rundir_write_fires(self):
        out = lint(WRITE019_TORN, rules=["WRITE019"])
        assert rule_ids(out) == ["WRITE019"]
        assert out[0].severity == "warning"
        assert "atomic_write_text" in out[0].message

    def test_atomic_write_helper_is_clean(self):
        assert lint(WRITE019_ATOMIC, rules=["WRITE019"]) == []

    def test_tmp_sibling_is_the_sanctioned_first_half(self):
        src = WRITE019_TORN.replace("'/progress.json'",
                                    "'/progress.json.tmp'")
        assert lint(src, rules=["WRITE019"]) == []

    def test_non_rundir_path_is_not_gated(self):
        src = WRITE019_TORN.replace("run_dir", "scratch")
        assert lint(src, rules=["WRITE019"]) == []


class TestHistoricalBugRegressionsV4:
    """ISSUE 20 acceptance: the historical concurrency bugs are
    re-detected statically — each as a positive fixture plus the
    fixed-code negative — and the shipped trees (which contain the
    FIXES) lint clean under the new families."""

    def test_pr12_backlog_seen_stomping_detected(self):
        out = lint(ATOM017_BACKLOG_SEEN, rules=["ATOM017"])
        assert [f.rule for f in out] == ["ATOM017"]

    def test_pr12_fixed_shape_is_clean(self):
        assert lint(ATOM017_BACKLOG_FIXED, rules=["ATOM017"]) == []

    def test_prestart_then_cross_thread_mutation_detected(self):
        out = lint(RACE016_CROSS_ROLE, rules=["RACE016"])
        assert [f.rule for f in out] == ["RACE016"]

    def test_queue_handoff_twin_is_clean(self):
        assert lint(RACE016_QUEUE_HANDOFF, rules=["RACE016"]) == []

    def test_real_serving_and_observability_trees_are_clean(self):
        """The shipped serving/observability/batchjobs code (which
        contains the fix-pass) passes the v4 families."""
        from analytics_zoo_tpu.analysis import analyze_paths
        findings, errors = analyze_paths(
            [os.path.join(REPO_ROOT, "analytics_zoo_tpu", sub)
             for sub in ("serving", "observability", "batchjobs")],
            root=REPO_ROOT,
            rule_ids=["RACE016", "ATOM017", "PUBLISH018", "WRITE019"])
        assert errors == []
        assert findings == [], [f.render() for f in findings]


class TestSarifExport:
    def test_sarif_document_schema_and_results(self, tmp_path,
                                               capsys):
        dirty = tmp_path / "dirty.py"
        dirty.write_text(DIRTY)
        out_file = tmp_path / "report.sarif"
        rc = zoolint_main(["--sarif", str(out_file), "--root",
                           str(tmp_path), str(dirty)])
        capsys.readouterr()
        assert rc == 1
        doc = json.loads(out_file.read_text())
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        assert run["tool"]["driver"]["name"] == "zoolint"
        rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
        assert {"JIT001", "DONATE012", "ACK013", "RES015"} <= rule_ids
        assert run["results"], "findings must be exported"
        res = run["results"][0]
        assert res["ruleId"] == "JIT001"
        assert res["level"] == "error"
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == "dirty.py"
        assert loc["region"]["startLine"] == 4

    def test_sarif_clean_run_has_empty_results(self, tmp_path,
                                               capsys):
        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        out_file = tmp_path / "report.sarif"
        assert zoolint_main(["--sarif", str(out_file), "--root",
                             str(tmp_path), str(clean)]) == 0
        capsys.readouterr()
        doc = json.loads(out_file.read_text())
        assert doc["runs"][0]["results"] == []


class TestChangedOnly:
    def _git_repo(self, tmp_path):
        def git(*args):
            proc = subprocess.run(
                ["git", "-C", str(tmp_path), *args],
                capture_output=True, text=True)
            assert proc.returncode == 0, proc.stderr
            return proc.stdout
        git("init", "-q")
        git("config", "user.email", "ci@example.com")
        git("config", "user.name", "ci")
        return git

    def test_reports_only_changed_files(self, tmp_path, capsys):
        git = self._git_repo(tmp_path)
        (tmp_path / "committed_dirty.py").write_text(DIRTY)
        (tmp_path / "stable.py").write_text("x = 1\n")
        git("add", "-A")
        git("commit", "-qm", "seed")
        # modify one file; the committed-dirty one is NOT re-reported
        (tmp_path / "stable.py").write_text(
            DIRTY.replace("def f", "def h"))
        rc = zoolint_main(["--changed-only", "--root", str(tmp_path),
                           str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "stable.py" in out
        assert "committed_dirty.py" not in out

    def test_untracked_files_are_included(self, tmp_path, capsys):
        git = self._git_repo(tmp_path)
        (tmp_path / "a.py").write_text("x = 1\n")
        git("add", "-A")
        git("commit", "-qm", "seed")
        (tmp_path / "fresh.py").write_text(DIRTY)
        rc = zoolint_main(["--changed-only", "--root", str(tmp_path),
                           str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 1 and "fresh.py" in out

    def test_no_changes_is_clean_and_fast(self, tmp_path, capsys):
        git = self._git_repo(tmp_path)
        (tmp_path / "committed_dirty.py").write_text(DIRTY)
        git("add", "-A")
        git("commit", "-qm", "seed")
        rc = zoolint_main(["--changed-only", "--root", str(tmp_path),
                           str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 0 and "clean" in out

    def test_changed_file_still_sees_full_project_facts(
            self, tmp_path, capsys):
        """The point of parse-everything/report-changed: a finding in
        a changed file that only exists because of an UNCHANGED
        module's facts (an imported jit's donation spec) must still
        fire."""
        git = self._git_repo(tmp_path)
        (tmp_path / "prog.py").write_text(
            "from analytics_zoo_tpu.compile import engine_jit\n"
            "def _f(state, b):\n"
            "    return state\n"
            "step = engine_jit(_f, donate_argnums=(0,))\n")
        git("add", "-A")
        git("commit", "-qm", "seed")
        (tmp_path / "driver.py").write_text(
            "from prog import step\n"
            "def run(state, batches):\n"
            "    for b in batches:\n"
            "        out = step(state, b)\n"
            "    return state\n")
        rc = zoolint_main(["--changed-only", "--rules", "DONATE012",
                           "--root", str(tmp_path), str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "driver.py" in out and "DONATE012" in out

    def test_root_below_git_toplevel_still_sees_changes(
            self, tmp_path, capsys):
        """Regression: ``git diff --name-only`` reports
        TOPLEVEL-relative paths while the analyzer keys on
        --root-relative ones — with --root pointing at a package
        subdir the fast path once matched nothing and printed
        'clean' over real findings."""
        git = self._git_repo(tmp_path)
        sub = tmp_path / "pkg"
        sub.mkdir()
        (sub / "mod.py").write_text("x = 1\n")
        git("add", "-A")
        git("commit", "-qm", "seed")
        (sub / "mod.py").write_text(DIRTY)
        rc = zoolint_main(["--changed-only", "--root", str(sub),
                           str(sub)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "mod.py" in out and "JIT001" in out

    def test_git_config_proofing_quotepath_and_relative(
            self, tmp_path, capsys):
        """Regression: git's default core.quotePath octal-escapes
        non-ASCII names and a user-level diff.relative rebases the
        output — either made the rebasing match nothing and the fast
        path print 'clean' over real findings.  The invocation pins
        both configs off."""
        git = self._git_repo(tmp_path)
        git("config", "diff.relative", "true")   # hostile user config
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        name = "héllo.py"                   # quotePath bait
        (pkg / name).write_text("x = 1\n")
        git("add", "-A")
        git("commit", "-qm", "seed")
        (pkg / name).write_text(DIRTY)
        rc = zoolint_main(["--changed-only", "--root", str(pkg),
                           str(pkg)])
        out = capsys.readouterr().out
        assert rc == 1
        assert name in out and "JIT001" in out

    def test_ref_vs_path_ambiguity_fails_loudly(self, tmp_path,
                                                capsys, monkeypatch):
        """A --changed-only value naming BOTH a git ref and an
        existing path must not silently pick either side (a branch
        named like a directory once linted against the wrong
        base)."""
        git = self._git_repo(tmp_path)
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "mod.py").write_text("x = 1\n")
        git("add", "-A")
        git("commit", "-qm", "seed")
        git("branch", "pkg")    # ref AND path
        monkeypatch.chdir(tmp_path)
        rc = zoolint_main(["--root", str(tmp_path),
                           "--changed-only", "pkg", str(tmp_path)])
        err = capsys.readouterr().err
        assert rc == 2 and "disambiguate" in err

    def test_donating_closure_definition_is_not_a_read(self):
        """Regression: a nested def/lambda referencing a donated
        name is DEFINED at the statement, not run — scanning its
        body at the def site minted error-severity false
        positives."""
        src = (
            "from analytics_zoo_tpu.compile import engine_jit\n"
            "def _f(state, b):\n"
            "    return state\n"
            "step = engine_jit(_f, donate_argnums=(0,))\n"
            "def run(state, b):\n"
            "    def helper():\n"
            "        return step(state, b)\n"
            "    audit(state)\n"
            "    return helper\n")
        assert lint(src, rules=["DONATE012"]) == []

    def test_missing_target_in_json_mode_stays_machine_readable(
            self, tmp_path, capsys):
        """The changed-only missing-target failure must honor --json
        like the full path does (check_static json.loads the
        stdout)."""
        git = self._git_repo(tmp_path)
        (tmp_path / "a.py").write_text("x = 1\n")
        git("add", "-A")
        git("commit", "-qm", "seed")
        rc = zoolint_main(["--json", "--changed-only", "--root",
                           str(tmp_path),
                           str(tmp_path / "no_such_dir")])
        out = capsys.readouterr().out
        assert rc == 1
        doc = json.loads(out)
        assert doc["total"] == 0
        assert any("no such file" in e for e in doc["errors"])

    def test_write_baseline_rejects_changed_only(self, tmp_path,
                                                 capsys):
        """A baseline written from a changed-files-only run would
        silently drop every unchanged file's acknowledged debt."""
        git = self._git_repo(tmp_path)
        (tmp_path / "a.py").write_text("x = 1\n")
        git("add", "-A")
        git("commit", "-qm", "seed")
        rc = zoolint_main(["--changed-only", "--write-baseline",
                           str(tmp_path / "b.json"), "--root",
                           str(tmp_path), str(tmp_path)])
        err = capsys.readouterr().err
        assert rc == 2 and "full run" in err

    def test_bare_flag_before_positional_paths(self, tmp_path,
                                               capsys):
        """Regression: nargs='?' let a bare --changed-only swallow
        the first positional path as its GITREF — the DOCUMENTED
        invocation ('zoolint --changed-only pkg ...') died on 'bad
        revision pkg'.  A captured value naming an existing path is
        a path; --changed-only=REF passes a ref unambiguously."""
        git = self._git_repo(tmp_path)
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "mod.py").write_text("x = 1\n")
        git("add", "-A")
        git("commit", "-qm", "seed")
        (pkg / "mod.py").write_text(DIRTY)
        rc = zoolint_main(["--root", str(tmp_path), "--changed-only",
                           str(pkg)])
        out = capsys.readouterr().out
        assert rc == 1 and "mod.py" in out and "JIT001" in out

    def test_no_changes_still_fails_on_missing_targets(
            self, tmp_path, capsys):
        """Regression: the no-changes fast path once returned 0
        without validating the CLI paths — a typo'd target turned
        the pre-commit gate into a permanent no-op on every clean
        worktree."""
        git = self._git_repo(tmp_path)
        (tmp_path / "a.py").write_text("x = 1\n")
        git("add", "-A")
        git("commit", "-qm", "seed")
        rc = zoolint_main(["--changed-only", "--root", str(tmp_path),
                           str(tmp_path / "no_such_dir")])
        err = capsys.readouterr().err
        assert rc == 1 and "no such file" in err

    def test_outside_a_git_tree_fails_loudly(self, tmp_path, capsys):
        sub = tmp_path / "not_a_repo"
        sub.mkdir()
        (sub / "a.py").write_text("x = 1\n")
        rc = zoolint_main(["--changed-only", "--root", str(sub),
                           str(sub)])
        capsys.readouterr()
        assert rc == 2

    def test_stale_baseline_enforcement_skipped(self, tmp_path,
                                                capsys):
        """Unchanged files are not re-analyzed, so their baseline
        entries are unmatched BY CONSTRUCTION — the only-shrink rule
        must not fire in the fast path (the full gate still enforces
        it)."""
        git = self._git_repo(tmp_path)
        dirty = tmp_path / "committed_dirty.py"
        dirty.write_text(DIRTY)
        git("add", "-A")
        git("commit", "-qm", "seed")
        baseline = tmp_path / "base.json"
        findings = lint(DIRTY)
        write_baseline(str(baseline), findings)
        (tmp_path / "new_clean.py").write_text("x = 1\n")
        rc = zoolint_main(["--changed-only", "--baseline",
                           str(baseline), "--root", str(tmp_path),
                           str(tmp_path)])
        capsys.readouterr()
        assert rc == 0


class TestReadmeCatalogDrift:
    def test_readme_table_matches_registry(self):
        """analysis/README.md's rule table is generated from the
        registry; regenerating must yield exactly the committed block
        (ISSUE 15 satellite: the PR 7 help text drifted for two
        releases — this makes drift a test failure)."""
        from analytics_zoo_tpu.analysis.cli import readme_rule_table
        readme = open(os.path.join(
            REPO_ROOT, "analytics_zoo_tpu", "analysis",
            "README.md"), encoding="utf-8").read()
        begin = readme.index("rule-table:begin")
        begin = readme.index("\n", begin) + 1
        end = readme.index("<!-- rule-table:end -->")
        committed = readme[begin:end].strip()
        assert committed == readme_rule_table().strip()
