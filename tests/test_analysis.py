"""Fixture suite for the ``repro.analysis`` invariant linter.

One known-bad snippet per pass (asserted to flag), one pragma-suppressed
variant (asserted clean), pass-precision checks against the idioms the
real tree uses, and the meta-test: the full ``src/`` tree lints clean at
HEAD — the acceptance bar the tier-1 gate enforces.
"""

import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import all_passes, lint_paths, lint_source
from repro.analysis.passes import (
    CacheTierPass,
    ChargeAccountingPass,
    GenerationDisciplinePass,
    KernelPurityPass,
    TraceSchemaPass,
)

REPO = Path(__file__).resolve().parent.parent


def findings(src, path="x.py", passes=None):
    return lint_source(src, path, passes or all_passes())


def ids(fs):
    return {f.pass_id for f in fs}


# --------------------------------------------------------- charge pass --
BAD_CHARGE = """
def sneak_read(dev, n):
    return dev.read_small(n)  # uncharged I/O
"""


def test_charge_flags_direct_device_read():
    fs = findings(BAD_CHARGE, passes=[ChargeAccountingPass()])
    assert len(fs) == 1 and fs[0].pass_id == "charge-accounting"
    assert "read_small" in fs[0].message


def test_charge_flags_iostats_poke():
    fs = findings(
        "def f(st):\n    st.read_bytes += 4096\n",
        passes=[ChargeAccountingPass()],
    )
    assert ids(fs) == {"charge-accounting"}


def test_charge_allows_chokepoint_modules():
    fs = findings(
        BAD_CHARGE,
        path="src/repro/core/stream.py",
        passes=[ChargeAccountingPass()],
    )
    assert fs == []


def test_charge_pragma_suppresses():
    src = (
        "def f(dev, n):\n"
        "    return dev.read_small(n)"
        "  # repro-lint: allow(charge-accounting) test harness\n"
    )
    assert findings(src, passes=[ChargeAccountingPass()]) == []


# ---------------------------------------------------------- trace pass --
BAD_TRACE_KEY = """
class S:
    def f(self):
        self.last_trace["wavez"] = 3
"""

BAD_TRACE_BOOL = """
class S:
    def f(self, trace, stopped):
        trace["early_terminated"] += any(stopped)
"""


def test_trace_flags_undeclared_key():
    fs = findings(BAD_TRACE_KEY, passes=[TraceSchemaPass()])
    assert len(fs) == 1 and "wavez" in fs[0].message


def test_trace_flags_bool_counter():
    fs = findings(BAD_TRACE_BOOL, passes=[TraceSchemaPass()])
    assert len(fs) == 1 and "early_terminated" in fs[0].message


def test_trace_tracks_local_bound_to_block():
    src = (
        "class S:\n"
        "    def f(self):\n"
        "        t = {'queries': 1, 'bogus_key': 2}\n"
        "        self.last_trace['topk'] = t\n"
    )
    fs = findings(src, passes=[TraceSchemaPass()])
    assert len(fs) == 1 and "bogus_key" in fs[0].message


def test_trace_tracks_subscript_write_through_binding():
    # the rt = route_trace(); rt[k] = ...; last_trace['replicas'] = rt idiom
    src = (
        "class S:\n"
        "    def f(self):\n"
        "        rt = self.reader.route_trace()\n"
        "        rt['failovers_batch'] = 1\n"
        "        rt['not_a_replica_key'] = 2\n"
        "        self.last_trace['replicas'] = rt\n"
    )
    fs = findings(src, passes=[TraceSchemaPass()])
    assert len(fs) == 1 and "not_a_replica_key" in fs[0].message


def test_trace_conditional_key_checks_both_arms():
    src = (
        "class S:\n"
        "    def f(self, trace, ranked):\n"
        "        trace['threshold_stops' if ranked else 'bogus_stop'] += 1\n"
    )
    fs = findings(src, passes=[TraceSchemaPass()])
    assert len(fs) == 1 and "bogus_stop" in fs[0].message


def test_trace_declared_keys_clean():
    src = (
        "class S:\n"
        "    def f(self, trace):\n"
        "        trace['waves'] += 1\n"
        "        self.last_trace['snapshot'] = [1]\n"
    )
    assert findings(src, passes=[TraceSchemaPass()]) == []


def test_trace_pragma_suppresses():
    src = (
        "class S:\n"
        "    def f(self):\n"
        "        self.last_trace['wavez'] = 3"
        "  # repro-lint: allow(trace-schema) migration shim\n"
    )
    assert findings(src, passes=[TraceSchemaPass()]) == []


def test_trace_accepts_declared_spans_and_the_spans_block():
    src = (
        "class S:\n"
        "    def f(self, rec):\n"
        "        with rec.span('search.plan'):\n"
        "            pass\n"
        "        with self._spans.span('index.decode', 12):\n"
        "            pass\n"
        "        rec.tally('index.decode', parallel=1, parallel_bytes=12)\n"
        "        self.last_trace['spans'] = rec.totals()\n"
    )
    assert findings(src, passes=[TraceSchemaPass()]) == []


@pytest.mark.parametrize("call,needle", [
    ("rec.span('search.plann')", "search.plann"),
    ("self._spans.span(name)", "literal"),
    ("rec.tally('index.decod', parallel=1)", "index.decod"),
    ("rec.tally('index.decode', parallel_ms=1)", "parallel_ms"),
])
def test_trace_flags_undeclared_or_computed_span_name(call, needle):
    src = (
        "class S:\n"
        "    def f(self, rec, name):\n"
        f"        with {call}:\n"
        "            pass\n"
    )
    fs = findings(src, passes=[TraceSchemaPass()])
    assert len(fs) == 1 and needle in fs[0].message


def test_runtime_and_static_registries_cannot_drift():
    # the runtime checker imports THE SAME schema object the static pass
    # reads, so a key added in one place only is caught on both sides
    from repro.search import service
    from repro.search.schema import validate_trace

    assert service.validate_trace is validate_trace
    assert validate_trace({"bogus": 1})
    assert validate_trace({"snapshot": [1], "topk": {"queries": 0}}) == ""


# ----------------------------------------------------- generation pass --
BAD_GENERATION_WRITE = """
def hijack(idx):
    idx.generation = 7
"""

BAD_NPARTS_SNAPSHOT = """
def pin(idx):
    snapshot_gen = idx.n_parts
    return snapshot_gen
"""


def test_generation_flags_outside_write():
    fs = findings(BAD_GENERATION_WRITE, passes=[GenerationDisciplinePass()])
    assert len(fs) == 1 and ".generation" in fs[0].message


def test_generation_allows_inverted_index():
    fs = findings(
        "class I:\n    def add_part(self):\n        self.generation += 1\n",
        path="src/repro/core/inverted_index.py",
        passes=[GenerationDisciplinePass()],
    )
    assert fs == []


def test_generation_flags_n_parts_as_snapshot():
    fs = findings(BAD_NPARTS_SNAPSHOT, passes=[GenerationDisciplinePass()])
    assert len(fs) == 1 and "n_parts" in fs[0].message


def test_generation_flags_n_parts_compare_and_restore():
    src = (
        "def check(idx, snap_gen):\n"
        "    if idx.n_parts != snap_gen:\n"
        "        idx.restore_generation(idx.n_parts)\n"
    )
    fs = findings(src, passes=[GenerationDisciplinePass()])
    assert len(fs) == 2


def test_generation_flags_persisted_n_parts():
    src = "def manifest(idx):\n    return {'generation_vector': [idx.n_parts]}\n"
    fs = findings(src, passes=[GenerationDisciplinePass()])
    assert len(fs) == 1 and "persisting" in fs[0].message


def test_generation_plain_part_count_is_fine():
    # n_parts used as a size, not a coordinate: no finding
    src = "def empty(idx):\n    return idx.n_parts == 0\n"
    assert findings(src, passes=[GenerationDisciplinePass()]) == []


def test_generation_pragma_suppresses():
    src = (
        "def hijack(idx):\n"
        "    idx.generation = 7"
        "  # repro-lint: allow(generation-discipline) test fixture\n"
    )
    assert findings(src, passes=[GenerationDisciplinePass()]) == []


# ---------------------------------------------------------- cache pass --
BAD_CACHE_POKE = """
def poke(cache, slot, arr):
    cache._map[slot] = arr
"""


def test_cache_flags_tier_poke_outside():
    fs = findings(BAD_CACHE_POKE, passes=[CacheTierPass()])
    assert fs and all(f.pass_id == "cache-tier" for f in fs)


def test_cache_flags_outside_admission():
    fs = findings(
        "def admit(cache, k, pre, tok):\n"
        "    cache.put_partial('ns', k, pre, tok)\n",
        passes=[CacheTierPass()],
    )
    assert len(fs) == 1 and "put_partial" in fs[0].message


def test_cache_inside_requires_frozen():
    src = (
        "class PostingCache:\n"
        "    def put(self, slot, arr):\n"
        "        self._map[slot] = arr\n"
    )
    fs = findings(
        src, path="src/repro/search/reader.py", passes=[CacheTierPass()]
    )
    assert len(fs) == 1 and "_frozen" in fs[0].message


def test_cache_inside_frozen_name_tracking_clean():
    src = (
        "class PostingCache:\n"
        "    def put(self, slot, arr):\n"
        "        arr = _frozen(arr.view())\n"
        "        self._map[slot] = arr\n"
    )
    fs = findings(
        src, path="src/repro/search/reader.py", passes=[CacheTierPass()]
    )
    assert fs == []


def test_cache_pragma_suppresses():
    src = (
        "def poke(cache, slot, arr):\n"
        "    cache._map[slot] = arr"
        "  # repro-lint: allow(cache-tier)白box test\n"
    )
    assert findings(src, passes=[CacheTierPass()]) == []


# --------------------------------------------------------- kernel pass --
BAD_KERNEL_TIME = """
import time

def kernel(x):
    return x * time.time()
"""


def test_kernel_flags_time_import_in_kernel_module():
    fs = findings(
        BAD_KERNEL_TIME,
        path="src/repro/kernels/foo/kernel.py",
        passes=[KernelPurityPass()],
    )
    assert fs and all(f.pass_id == "kernel-purity" for f in fs)


def test_kernel_flags_unsorted_dict_iteration():
    src = (
        "def decode(groups):\n"
        "    out = []\n"
        "    for k, v in groups.items():\n"
        "        out.append(v)\n"
        "    return out\n"
    )
    fs = findings(
        src, path="src/repro/kernels/foo/ops.py", passes=[KernelPurityPass()]
    )
    assert len(fs) == 1 and "items" in fs[0].message
    sorted_src = src.replace("groups.items()", "sorted(groups.items())")
    assert findings(
        sorted_src, path="src/repro/kernels/foo/ops.py",
        passes=[KernelPurityPass()],
    ) == []


def test_kernel_flags_traced_branch_in_jitted_fn():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x > 0:\n"
        "        return x\n"
        "    return -x\n"
    )
    fs = findings(src, passes=[KernelPurityPass()])
    assert len(fs) == 1 and "traced value `x`" in fs[0].message


def test_kernel_static_args_exempt():
    # the flash_attention idiom: branch on a static_argnames parameter
    src = (
        "from functools import partial\n"
        "import jax\n"
        "@partial(jax.jit, static_argnames=('causal', 'bq'))\n"
        "def f(q, causal, bq):\n"
        "    if causal:\n"
        "        bq = min(bq, q.shape[0])\n"
        "    return q\n"
    )
    assert findings(src, passes=[KernelPurityPass()]) == []


def test_kernel_static_argnums_exempt():
    src = (
        "import functools, jax\n"
        "@functools.partial(jax.jit, static_argnums=2)\n"
        "def f(vals, segs, n):\n"
        "    if n > 4:\n"
        "        return vals\n"
        "    return segs\n"
    )
    assert findings(src, passes=[KernelPurityPass()]) == []


def test_kernel_shape_access_exempt():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x.shape[0] > 1:\n"
        "        return x\n"
        "    return x\n"
    )
    assert findings(src, passes=[KernelPurityPass()]) == []


def test_kernel_jit_wrap_expression_detected():
    # the scoring.py idiom: def f(...) ... return jax.jit(f)
    src = (
        "import jax\n"
        "def make(k):\n"
        "    def f(x):\n"
        "        if x > k:\n"
        "            return x\n"
        "        return -x\n"
        "    return jax.jit(f)\n"
    )
    fs = findings(src, passes=[KernelPurityPass()])
    assert len(fs) == 1 and "traced value `x`" in fs[0].message


def test_kernel_pragma_suppresses():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x > 0:"
        "  # repro-lint: allow(kernel-purity) concrete under vmap\n"
        "        return x\n"
        "    return -x\n"
    )
    assert findings(src, passes=[KernelPurityPass()]) == []


# -------------------------------------------------- engine & interface --
def test_pragma_is_pass_scoped():
    # an allow() for one pass must not silence another on the same line
    src = (
        "def f(dev, idx, n):\n"
        "    idx.generation = dev.read_small(n)"
        "  # repro-lint: allow(charge-accounting) half excuse\n"
    )
    fs = findings(src)
    assert ids(fs) == {"generation-discipline"}


def test_pragma_star_silences_all():
    src = (
        "def f(dev, idx, n):\n"
        "    idx.generation = dev.read_small(n)"
        "  # repro-lint: allow(*) fixture\n"
    )
    assert findings(src) == []


def test_finding_render_format():
    fs = findings(BAD_CHARGE, path="pkg/mod.py")
    assert fs[0].render().startswith("pkg/mod.py:3 charge-accounting ")


def test_syntax_error_reported_not_raised():
    fs = findings("def broken(:\n")
    assert len(fs) == 1 and fs[0].pass_id == "parse-error"


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(BAD_CHARGE)
    env_src = str(REPO / "src")
    for target, expect in ((str(bad), 1), (str(tmp_path / "none"), 0)):
        proc = subprocess.run(
            [sys.executable, "-m", "repro.analysis", target],
            capture_output=True, text=True,
            env={"PYTHONPATH": env_src, "PATH": "/usr/bin:/bin"},
        )
        assert proc.returncode == expect, proc.stderr
    assert "charge-accounting" in subprocess.run(
        [sys.executable, "-m", "repro.analysis", str(bad)],
        capture_output=True, text=True,
        env={"PYTHONPATH": env_src, "PATH": "/usr/bin:/bin"},
    ).stdout


# ------------------------------------------------------------ meta-test --
def test_full_src_tree_lints_clean():
    fs = lint_paths([str(REPO / "src")])
    assert fs == [], "\n".join(f.render() for f in fs)
