"""tpulint v3 pass 3 (tools/tpulint/shapeflow.py): the symbolic
shape-flow lattice and its four gate rules, plus the CLI/workflow
satellites that ride on it.

Fixture tests pin each rule's exact firing semantics (and each
contract's suppression semantics); the soundness test cross-checks the
abstract dim classification against ``jax.eval_shape`` on the REAL
executor program factories; the census test cross-validates R017's
DataDependent verdicts against the program observatory's shape-key
census on a live (CPU-mesh) node — the dynamic ground truth for what
actually rides a program cache key.
"""
import json
import os
import shutil
import stat
import subprocess
import sys

import numpy as np
import pytest

from tools.tpulint import lint_sources
from tools.tpulint.analyzer import Violation
from tools.tpulint.project import analyze_sources, build_project
from tools.tpulint import shapeflow

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# R017 — recompile storm
# ---------------------------------------------------------------------------

class TestR017RecompileStorm:
    AOT = "def wrap(fn, program, key):\n    return fn\n"
    FACTORY = """
from pkg import aot

_CACHE = {}

def _score_program(Q, D):
    key = (Q, D)
    fn = _CACHE.get(key)
    if fn is None:
        def body(x):
            return x
        fn = aot.wrap(body, "score", key)
        _CACHE[key] = fn
    return fn
"""

    def test_datadep_dim_into_factory_flagged_bucketed_clean(self):
        vs = lint_sources({
            "pkg/aot.py": self.AOT,
            "pkg/factory.py": self.FACTORY,
            "pkg/host.py": """
from pkg.factory import _score_program
from elasticsearch_tpu.utils.shapes import pow2_bucket

def bad(queries, docs):
    Q = len(queries)
    prog = _score_program(Q, 128)
    return prog(docs)

def good(queries, docs):
    Q = pow2_bucket(len(queries))
    prog = _score_program(Q, 128)
    return prog(docs)
""",
        })
        assert [(v.rule, v.path, v.line) for v in vs] == \
            [("R017", "pkg/host.py", 7)]
        assert "recompile" in vs[0].message

    def test_bucketed_contract_suppresses(self):
        vs = lint_sources({
            "pkg/aot.py": self.AOT,
            "pkg/factory.py": """
from pkg import aot

def _score_program(Q, D):
    def body(x):
        return x
    return aot.wrap(body, "score", (Q, D))
""",
            "pkg/host.py": """
from pkg.factory import _score_program

def declared(queries, docs):
    Q = len(queries)
    prog = _score_program(Q, 128)  # tpulint: bucketed
    return prog(docs)
""",
        })
        assert vs == []

    def test_jit_static_arg_and_interprocedural_flow(self):
        """The statics arm (a DataDependent value bound to a
        static_argnames param of a jit symbol) plus two-hop value flow:
        the ``len()`` is two calls away from the static binding."""
        vs = lint_sources({
            "s/mod.py": """
import jax
from functools import partial

@partial(jax.jit, static_argnames=("n",))
def padded(x, n):
    return x

def caller(x, data):
    n = len(data)
    return padded(x, n)
""",
            "s/indirect.py": """
from s.mod import padded

def layer1(x, data):
    m = len(data)
    return layer2(x, m)

def layer2(x, m):
    return padded(x, m)
""",
        })
        assert [(v.rule, v.path, v.line) for v in vs] == \
            [("R017", "s/indirect.py", 9), ("R017", "s/mod.py", 11)]


# ---------------------------------------------------------------------------
# R018 — padding soundness
# ---------------------------------------------------------------------------

class TestR018PaddingSoundness:
    def test_unmasked_reduction_in_collective_body(self):
        """Only the raw-operand sum fires: the jnp.where-validated, the
        mask-multiplied, and the unresolved-helper reductions are all
        clean (helpers give Unknown, never flagged)."""
        vs = lint_sources({"m/prog.py": """
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map

def build(mesh):
    def body(scores, live):
        totals = jnp.sum(scores, axis=1)
        masked = jnp.where(live, scores, 0.0)
        ok = jnp.sum(masked, axis=1)
        ok2 = jnp.sum(scores * live, axis=1)
        unk = jnp.sum(helper(scores))
        return totals + ok + ok2 + unk
    return shard_map(body, mesh=mesh, in_specs=(), out_specs=())
"""})
        assert [(v.rule, v.line) for v in vs] == [("R018", 7)]
        assert "mask" in vs[0].message

    def test_masked_contract_suppresses(self):
        vs = lint_sources({"m/prog.py": """
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map

def build(mesh):
    def body(scores, live):
        totals = jnp.sum(scores, axis=1)  # tpulint: masked
        return totals
    return shard_map(body, mesh=mesh, in_specs=(), out_specs=())
"""})
        assert vs == []


# ---------------------------------------------------------------------------
# R019 — dtype discipline
# ---------------------------------------------------------------------------

class TestR019DtypeDiscipline:
    def test_wide_dtypes_and_mxu_mixing_in_traced_code(self):
        """f64 spellings (astype and dtype= kw) and a bf16@f32 matmul
        fire inside jit; the same f64 spelling in plain host code is
        legal (numpy accumulators)."""
        vs = lint_sources({"t/mod.py": """
import jax
import jax.numpy as jnp
from functools import partial

@jax.jit
def bad_wide(x):
    return x.astype(jnp.float64)

@jax.jit
def bad_mixed(a, b):
    al = a.astype(jnp.bfloat16)
    bl = b.astype(jnp.float32)
    return al @ bl

@jax.jit
def bad_kw(x):
    return x + jnp.zeros((4,), dtype=jnp.float64)

@jax.jit
def good(x):
    return x.astype(jnp.float32)

def host_ok(x):
    return x.astype("float64")
"""})
        assert [(v.rule, v.line) for v in vs] == \
            [("R019", 8), ("R019", 14), ("R019", 18)]

    def test_cast_contract_suppresses(self):
        vs = lint_sources({"t/mod.py": """
import jax
import jax.numpy as jnp

@jax.jit
def declared(x):
    return x.astype(jnp.float64)  # tpulint: cast
"""})
        assert vs == []


# ---------------------------------------------------------------------------
# R020 — reservation leak
# ---------------------------------------------------------------------------

class TestR020ReservationLeak:
    RESIDENCY = """
class ResidencyRegistry:
    def track(self, n, label=""):
        return object()

    def _release(self, n):
        pass

RESIDENCY = ResidencyRegistry()
"""

    def test_token_form_risky_call_before_handoff(self):
        """A fallible call between track() and the store that hands the
        token off leaks the charge on exception; store-first and the
        try/except-release shapes are both clean."""
        vs = lint_sources({
            "r/residency.py": self.RESIDENCY,
            "r/user.py": """
from r.residency import RESIDENCY

def bad(data, store):
    tok = RESIDENCY.track(len(data), label="x")
    prepare(store)
    store["k"] = tok

def good_store_first(data, store):
    tok = RESIDENCY.track(len(data), label="x")
    store["k"] = tok
    prepare(store)

def good_protected(data, store):
    tok = RESIDENCY.track(len(data), label="x")
    try:
        prepare(store)
    except Exception:
        tok.close()
        raise
    store["k"] = tok
""",
        })
        assert [(v.rule, v.path, v.line) for v in vs] == \
            [("R020", "r/user.py", 5)]
        assert "exception" in vs[0].message

    def test_void_form_breaker_charge(self):
        """force() has no token: liability runs until an explicit
        release or a commit (attribute store / return)."""
        vs = lint_sources({
            "r/breakers.py": """
class CircuitBreaker:
    def force(self, n):
        pass

    def release(self, n):
        pass

BREAKER = CircuitBreaker()
""",
            "r/vuser.py": """
from r.breakers import BREAKER

class Holder:
    def bad(self, n, items):
        BREAKER.force(n)
        risky(items)
        self._committed = n

    def good_release(self, n, items):
        BREAKER.force(n)
        BREAKER.release(n)
        risky(items)

    def good_commit_first(self, n, items):
        BREAKER.force(n)
        self._committed = n
        risky(items)
""",
        })
        assert [(v.rule, v.line) for v in vs] == [("R020", 6)]


# ---------------------------------------------------------------------------
# the ShapeFlowReport view
# ---------------------------------------------------------------------------

class TestShapeFlowReport:
    def test_fixture_report(self):
        index, errors = analyze_sources({
            "pkg/aot.py": TestR017RecompileStorm.AOT,
            "pkg/factory.py": TestR017RecompileStorm.FACTORY,
            "pkg/host.py": """
from pkg.factory import _score_program
from elasticsearch_tpu.utils.shapes import pow2_bucket

def bad(queries, docs):
    Q = len(queries)
    prog = _score_program(Q, 128)
    return prog(docs)

def good(queries, docs):
    Q = pow2_bucket(len(queries))
    prog = _score_program(Q, 128)
    return prog(docs)
""",
        })
        assert errors == []
        rep = shapeflow.analyze(index)
        assert rep.factories == ["pkg.factory:_score_program"]
        # Q joins DataDependent (bad) with PaddedPow2 (good) → DataDep;
        # the literal 128 stays Concrete
        assert rep.factory_param_dims["pkg.factory:_score_program"] == \
            {"Q": "DataDependent", "D": "Concrete"}
        assert rep.dims_classified["DataDependent"] >= 1
        assert rep.dims_classified["PaddedPow2"] >= 1
        # memoized on the index (lint/bench/census share one evaluation)
        assert shapeflow.analyze(index) is rep

    def test_real_executor_factories_classified(self):
        """The adoption pass is visible in the abstract domain: the
        executor's five program factories exist as factories, and the
        bm25 cache-key dims are all PaddedPow2 — the Q-axis bucketing
        fix, as the analyzer sees it."""
        index, _errors = build_project(
            [os.path.join(REPO_ROOT, "elasticsearch_tpu")], root=REPO_ROOT)
        rep = shapeflow.analyze(index)
        pfx = "elasticsearch_tpu.parallel.executor:"
        for fac in ("_bm25_program", "_knn_program", "_maxsim_program",
                    "_dsl_program", "_psum_program"):
            assert pfx + fac in rep.factories, rep.factories
        bm25 = rep.factory_param_dims[pfx + "_bm25_program"]
        for p in ("Q", "T", "P", "D", "k"):
            assert bm25[p] == "PaddedPow2", (p, bm25)
        # nothing DataDependent reaches the bm25 key — the R017 claim
        assert "DataDependent" not in bm25.values()


# ---------------------------------------------------------------------------
# satellites: --prune-baseline, --changed rename fix, pre-commit hook
# ---------------------------------------------------------------------------

def _v(rule, path, line, snippet):
    return Violation(rule, path, line, 0, "msg", snippet)


class TestPruneBaseline:
    DOC = {"violations": [
        {"rule": "R001", "path": "a.py", "snippet": "x = foo()",
         "count": 2, "justification": "j"},
        {"rule": "R002", "path": "b.py", "snippet": "y = bar()",
         "count": 1, "justification": "j"},
    ]}

    def test_audit_reports_stale_without_touching_file(self, tmp_path):
        from tools.tpulint.baseline import prune_baseline

        bl = tmp_path / "baseline.json"
        bl.write_text(json.dumps(self.DOC))
        live = [_v("R001", "a.py", 3, "x = foo()")]
        stale = prune_baseline(live, str(bl), fix=False)
        # one of R001's two budgeted occurrences died, R002 entirely
        assert [(e["rule"], e["dead"]) for e in stale] == \
            [("R001", 1), ("R002", 1)]
        assert json.loads(bl.read_text()) == self.DOC

    def test_fix_rewrites_live_counts_only(self, tmp_path):
        from tools.tpulint.baseline import prune_baseline

        bl = tmp_path / "baseline.json"
        bl.write_text(json.dumps(self.DOC))
        live = [_v("R001", "a.py", 3, "x = foo()")]
        stale = prune_baseline(live, str(bl), fix=True)
        assert [e["rule"] for e in stale] == ["R001", "R002"]
        out = json.loads(bl.read_text())
        assert out["violations"] == [
            {"rule": "R001", "path": "a.py", "snippet": "x = foo()",
             "count": 1, "justification": "j"}]

    def test_fix_removes_file_when_nothing_survives(self, tmp_path):
        from tools.tpulint.baseline import prune_baseline

        bl = tmp_path / "baseline.json"
        bl.write_text(json.dumps(self.DOC))
        assert prune_baseline([], str(bl), fix=True)
        assert not bl.exists()

    def test_fully_live_baseline_is_clean(self, tmp_path):
        from tools.tpulint.baseline import prune_baseline

        bl = tmp_path / "baseline.json"
        bl.write_text(json.dumps(self.DOC))
        live = [_v("R001", "a.py", 3, "x = foo()"),
                _v("R001", "a.py", 9, "x = foo()"),
                _v("R002", "b.py", 4, "y = bar()")]
        assert prune_baseline(live, str(bl), fix=False) == []
        assert json.loads(bl.read_text()) == self.DOC


def _git(args, cwd):
    subprocess.run(
        ["git", "-c", "user.email=dev@example.com", "-c", "user.name=dev",
         *args], cwd=str(cwd), check=True, capture_output=True)


def test_changed_follows_renames(tmp_path, monkeypatch):
    """Regression for the --changed rename bug: --name-only reported a
    renamed file under its OLD (nonexistent) path, which was silently
    skipped — a rename that also edits the file dodged the gate. The
    status parser must surface the NEW path."""
    import tools.tpulint.__main__ as cli

    repo = tmp_path / "repo"
    repo.mkdir()
    _git(["init", "-q"], repo)
    (repo / "alpha.py").write_text("x = 1\n" * 40)
    (repo / "keep.py").write_text("z = 0\n")
    _git(["add", "-A"], repo)
    _git(["commit", "-qm", "c0"], repo)
    _git(["mv", "alpha.py", "beta.py"], repo)
    p = repo / "beta.py"
    p.write_text(p.read_text() + "y = 2\n")  # rename + edit
    _git(["add", "-A"], repo)
    monkeypatch.setattr(cli, "REPO_ROOT", str(repo))
    got = cli._changed_files("HEAD")
    assert got == ["beta.py"]


def test_precommit_hook_blocks_seeded_violation(tmp_path):
    """The shipped hook, run as git would run it, in a throwaway repo:
    exits 0 on a clean tree, exits 1 (blocking the commit) when an
    untracked module carries a violation, and leaves the SARIF record
    behind."""
    repo = tmp_path / "repo"
    shutil.copytree(os.path.join(REPO_ROOT, "tools"), str(repo / "tools"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    (repo / "elasticsearch_tpu").mkdir()
    (repo / "elasticsearch_tpu" / "__init__.py").write_text("")
    (repo / "bench.py").write_text("")
    _git(["init", "-q"], repo)
    _git(["add", "-A"], repo)
    _git(["commit", "-qm", "c0"], repo)
    hook = repo / "tools" / "tpulint" / "hooks" / "pre-commit"
    hook.chmod(hook.stat().st_mode | stat.S_IXUSR)
    env = dict(os.environ)
    env["PATH"] = os.path.dirname(sys.executable) + os.pathsep + \
        env.get("PATH", "")
    env.pop("PYTHONPATH", None)

    r = subprocess.run([str(hook)], cwd=str(repo), env=env,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr

    (repo / "elasticsearch_tpu" / "seeded.py").write_text(
        "import jax\n"
        "import jax.numpy as jnp\n"
        "\n"
        "@jax.jit\n"
        "def seeded(x):\n"
        "    return x.astype(jnp.float64)\n")
    r = subprocess.run([str(hook)], cwd=str(repo), env=env,
                       capture_output=True, text=True)
    assert r.returncode == 1, (r.stdout, r.stderr)
    assert "blocking commit" in r.stderr
    sarif = json.loads((repo / ".git" / "tpulint-precommit.sarif")
                       .read_text())
    rules = [res["ruleId"] for res in sarif["runs"][0]["results"]]
    assert "R019" in rules


# ---------------------------------------------------------------------------
# soundness: abstract dims vs jax.eval_shape on the real factories
# ---------------------------------------------------------------------------

def test_shapeflow_sound_vs_eval_shape(monkeypatch, eight_devices):
    """The lattice's operational claim, checked against JAX's own
    abstract evaluator: for pow2-bucketed cache-key dims, every factory
    program traces STATICALLY (eval_shape succeeds — no data-dependent
    shapes inside), and the output dims are functions of the key dims
    alone — so equal keys really do mean one compiled program, which is
    exactly what R017 protects. aot.wrap is stubbed to identity (its
    blob cache is orthogonal to shape semantics)."""
    import jax
    import jax.numpy as jnp

    from elasticsearch_tpu.parallel import aot
    from elasticsearch_tpu.parallel import executor as exmod
    from elasticsearch_tpu.parallel import shard_mesh

    monkeypatch.setattr(aot, "wrap", lambda fn, name, key: fn)
    mesh = shard_mesh(1)  # single slot: wrap == plain jit (no collectives)
    f32, i32, b8 = jnp.float32, jnp.int32, jnp.bool_
    S = jax.ShapeDtypeStruct

    for Q, T, D, k in [(4, 8, 64, 8), (8, 4, 128, 16)]:
        nnz, P, dims = 4 * D, 8, 8
        prog = exmod._bm25_program(mesh, {}, Q=Q, T=T, P=P, D=D, k=k)
        out = jax.eval_shape(prog, S((nnz,), i32), S((nnz,), f32),
                             S((Q, T), i32), S((Q, T), i32),
                             S((Q, T), f32), S((D,), b8))
        assert [o.shape for o in out] == [(Q, k)] * 3 + [(Q,)]

        prog = exmod._knn_program(mesh, {}, Q=Q, dims=dims, D=D, k=k,
                                  metric="dot")
        # (a dot_product slab has no stored row term: None in its place)
        out = jax.eval_shape(prog, S((Q, dims), f32), S((D, dims), f32),
                             None, S((D,), b8))
        assert [o.shape for o in out] == [(Q, k)] * 3

        prog = exmod._knn_program(mesh, {}, Q=Q, dims=dims, D=D, k=k,
                                  metric="l2_norm")
        out = jax.eval_shape(prog, S((Q, dims), f32), S((D, dims), f32),
                             S((D,), f32), S((D,), b8))
        assert [o.shape for o in out] == [(Q, k)] * 3

        prog = exmod._maxsim_program(mesh, {}, Q=Q, T=T, dims=dims, D=D,
                                     k=k, metric="dot")
        out = jax.eval_shape(prog, S((Q, T, dims), f32), S((D, dims), f32),
                             None, S((D,), b8))
        assert [o.shape for o in out] == [(Q, k)] * 3

    prog = exmod._psum_program(mesh, {}, (4, 5))
    out = jax.eval_shape(prog, S((4, 5), f32))
    assert out.shape == (4, 5)


# ---------------------------------------------------------------------------
# census cross-validation: R017 verdicts vs the observatory ground truth
# ---------------------------------------------------------------------------

def test_census_cross_validates_r017(eight_devices):
    """Dynamic ground truth for the static verdicts: run real searches
    with different query counts on a live 8-slot mesh, read the program
    observatory's shape-key census, and check that every cache-key dim
    the census actually saw VARY is classified non-Concrete by
    shapeflow — a dim the analyzer called Concrete but the census saw
    take two values would be a missed recompile storm."""
    from elasticsearch_tpu.analysis.registry import AnalysisRegistry
    from elasticsearch_tpu.index.doc_parser import DocumentParser
    from elasticsearch_tpu.index.mappings import Mappings
    from elasticsearch_tpu.index.segment import SegmentBuilder
    from elasticsearch_tpu.monitor.programs import REGISTRY, index_scope
    from elasticsearch_tpu.parallel import MeshSearchExecutor, shard_mesh

    mappings = Mappings({"properties": {"body": {"type": "text"}}})
    reg = AnalysisRegistry()
    rng = np.random.default_rng(11)
    vocab = [f"w{i}" for i in range(30)]
    docs = [" ".join(rng.choice(vocab, size=10)) for _ in range(64)]
    shards = []
    for i in range(8):
        parser = DocumentParser(mappings, reg)
        builder = SegmentBuilder(mappings)
        for j, text in enumerate(docs[i::8]):
            builder.add(parser.parse(str(j), {"body": text}))
        shards.append(builder.freeze())
    ex = MeshSearchExecutor(shard_mesh(8), shards)

    REGISTRY.reset()
    with index_scope("census_xval"):
        # 3 queries → Q bucket 4; 5 queries → Q bucket 8: the Q key
        # family takes two values in the census
        ex.search_terms("body", [[("w1", 1.0)]] * 3, k=10)
        ex.search_terms("body", [[("w2", 1.0)]] * 5, k=10)
    census = REGISTRY.census("census_xval")
    bm25 = [e for e in census if e["program"] == "mesh_bm25"]
    assert bm25, census

    seen = {}
    for e in bm25:
        for part in e["shapes"].split("|"):
            name, val = part.split("=")
            seen.setdefault(name, set()).add(val)
    assert len(seen.get("Q", ())) >= 2, seen  # census really saw Q vary

    index, _errors = build_project(
        [os.path.join(REPO_ROOT, "elasticsearch_tpu")], root=REPO_ROOT)
    rep = shapeflow.analyze(index)
    dims = rep.factory_param_dims[
        "elasticsearch_tpu.parallel.executor:_bm25_program"]
    for name, vals in seen.items():
        if len(vals) < 2 or name not in dims:
            continue
        assert dims[name] != "Concrete", (name, vals, dims)


# ---------------------------------------------------------------------------
# hybrid fusion fixture: fusion weights are traced operands, not statics
# ---------------------------------------------------------------------------

class TestR017HybridFusionWeights:
    """The hybrid stage-1 contract: per-request fusion parameters
    (weights, rank_constant, candidate cutoff) ride the program as traced
    operands. Letting the request's weight-vector arity reach the program
    cache key turns every weight-shape variation into a fresh trace —
    exactly R017's recompile storm."""

    def test_weight_arity_into_fuse_program_key_flagged(self):
        vs = lint_sources({
            "h/aot.py": TestR017RecompileStorm.AOT,
            "h/fuse.py": """
from h import aot

_JITTED = {}

def _fuse_program(W, D):
    key = (W, D)
    fn = _JITTED.get(key)
    if fn is None:
        def body(scores, weights):
            return scores
        fn = aot.wrap(body, "hybrid_fuse", key)
        _JITTED[key] = fn
    return fn
""",
            "h/exec.py": """
from h.fuse import _fuse_program

def hybrid_topk(scores, weights):
    W = len(weights)
    prog = _fuse_program(W, 4096)
    return prog(scores, weights)
""",
        })
        assert [(v.rule, v.path, v.line) for v in vs] == \
            [("R017", "h/exec.py", 6)]

    def test_fixed_arity_traced_weights_clean(self):
        # the shipped discipline: engine count is a config constant, the
        # weight VALUES are operands — nothing data-dependent reaches
        # the key
        vs = lint_sources({
            "h/aot.py": TestR017RecompileStorm.AOT,
            "h/fuse.py": """
from h import aot

N_ENGINES = 2
_JITTED = {}

def _fuse_program(D):
    key = (N_ENGINES, D)
    fn = _JITTED.get(key)
    if fn is None:
        def body(scores, weights):
            return scores
        fn = aot.wrap(body, "hybrid_fuse", key)
        _JITTED[key] = fn
    return fn
""",
            "h/exec.py": """
from h.fuse import _fuse_program

def hybrid_topk(scores, weights):
    prog = _fuse_program(4096)
    return prog(scores, weights)
""",
        })
        assert vs == []
