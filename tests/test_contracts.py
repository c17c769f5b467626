"""Whole-program contract extraction + RF014–RF016, proven in both
polarities.

The extractors are tested on synthetic module trees (no filesystem),
the checkers through ``analyze_paths`` over fixture trees on disk —
including the doctored rename of ``mesh/pack_formed`` the acceptance
criteria name: renaming EITHER the writer or the reader side must
fail loudly, naming the kind and both sites. Dynamic shapes
(non-constant kinds, ``**kwargs`` field sets, computed env defaults)
must degrade to manifest-visible warnings, never false errors.
"""

import ast
import json
import os
import textwrap

from rafiki_tpu.analysis import analyze_paths, load_builtin_checkers
from rafiki_tpu.analysis.contracts.envknobs import extract_env
from rafiki_tpu.analysis.contracts.journal import (
    extract_journal, missing_reader_fields, unknown_reader_keys,
    unread_writer_keys)
from rafiki_tpu.analysis.contracts.manifest import (
    build_manifest, dump_manifest, manifest_for_paths)
from rafiki_tpu.analysis.contracts.telem import (
    documented_names, extract_telemetry, is_documented, join_prom_golden)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

load_builtin_checkers()


class _Mod:
    def __init__(self, path, src):
        self.path = path
        self.tree = ast.parse(textwrap.dedent(src))


def _mods(**files):
    return [_Mod(p.replace("__", "/") + ".py", s)
            for p, s in files.items()]


def _write_tree(tmp_path, files):
    paths = []
    tmp_path.mkdir(parents=True, exist_ok=True)
    for name, src in files.items():
        f = tmp_path / name
        f.write_text(textwrap.dedent(src))
        paths.append(str(f))
    return paths


def _unsup(result, checker=None):
    return [f for f in result.unsuppressed
            if checker is None or f.checker_id == checker]


# ---------------------------------------------------------------------------
# journal extraction
# ---------------------------------------------------------------------------


def test_writer_extraction_constants_and_fields():
    jc = extract_journal(_mods(w="""
        KIND = "advisor"
        def go(journal, advisor):
            journal.record("mesh", "pack_formed", chip=0, k=4)
            journal.record(KIND, "propose", knobs={}, **_ident(advisor))
        """))
    pairs = jc.writer_pairs()
    assert pairs["mesh/pack_formed"][0].fields == ("chip", "k")
    assert not pairs["mesh/pack_formed"][0].dynamic_fields
    # module-constant kind resolves; **kwargs marks the set open
    assert pairs["advisor/propose"][0].dynamic_fields
    assert jc.fields_written("advisor", "propose") is None
    assert "chip" in jc.fields_written("mesh", "pack_formed")


def test_dynamic_kind_degrades_to_manifest_warning_not_error():
    jc = extract_journal(_mods(w="""
        def go(journal, kind):
            journal.record(kind, "x", a=1)
        """))
    assert not jc.writers
    assert len(jc.dynamic_writers) == 1
    # and a constant kind with a dynamic name is a wildcard writer
    jc2 = extract_journal(_mods(w="""
        def go(journal, ev):
            journal.record("event", ev, a=1)
        """))
    assert jc2.writer_pairs().keys() == {"event/*"}
    assert jc2.wildcard_kinds() == {"event"}


def test_reader_filter_guard_alias_and_projection():
    jc = extract_journal(_mods(r="""
        FIELDS = ("chip", "packing_key")
        def read(recs):
            out = []
            for r in recs:
                if r.get("kind") != "mesh":
                    continue
                kind, name = r.get("kind"), r.get("name")
                if name == "pack_formed":
                    out.append({f: r.get(f) for f in ("chip", "fill_ratio")})
            return out
        """))
    pairs = jc.reader_pairs()
    # the guard-continue flips to a positive kind constraint...
    assert "mesh/*" in pairs
    # ...and the alias comparison refines it to the pair, with the
    # projection idiom's looped constant fields attached
    site = pairs["mesh/pack_formed"][0]
    assert site.fields == ["chip", "fill_ratio"]


def test_reader_required_kinds_and_membership():
    jc = extract_journal(_mods(r="""
        REQUIRED_KINDS = ("perf/step", "mesh/pack_formed")
        def scan(recs):
            return [r for r in recs
                    if r.get("kind") == "mesh"
                    and r.get("name") in ("repack", "chip_lost")]
        """))
    pairs = jc.reader_pairs()
    assert {"perf/step", "mesh/pack_formed"} <= set(pairs)
    assert pairs["perf/step"][0].source == "required-kinds"
    assert {"mesh/repack", "mesh/chip_lost"} <= set(pairs)


def test_helper_predicate_call_sites_become_readers():
    jc = extract_journal(_mods(r="""
        def _has(recs, kind, name):
            return any(r.get("kind") == kind and r.get("name") == name
                       for r in recs)
        def check(recs):
            assert _has(recs, "mesh", "repack")
            assert _has(recs, "recovery", "rehydrated")
        """))
    pairs = jc.reader_pairs()
    assert pairs["mesh/repack"][0].source == "helper-call"
    assert "recovery/rehydrated" in pairs


def test_joins_unread_unknown_and_missing_fields():
    jc = extract_journal(_mods(w="""
        def go(journal):
            journal.record("mesh", "pack_formed", chip=0)
            journal.record("orphan", "write_only", a=1)
        """, r="""
        def read(recs):
            for r in recs:
                if r.get("kind") == "mesh" and r.get("name") == "pack_formed":
                    print(r.get("chip"), r.get("fill_ratio"))
                if r.get("kind") == "ghost":
                    pass
        """))
    assert unread_writer_keys(jc) == ["orphan/write_only"]
    assert unknown_reader_keys(jc) == ["ghost/*"]
    [(site, missing)] = missing_reader_fields(jc)
    assert site.key == "mesh/pack_formed" and missing == ["fill_ratio"]


# ---------------------------------------------------------------------------
# env-knob extraction
# ---------------------------------------------------------------------------


def test_env_read_shapes_defaults_and_parse_types():
    env = extract_env(_mods(m="""
        import os
        from pathlib import Path
        ENV_VAR = "RAFIKI_INDIRECT"
        a = int(os.environ.get("RAFIKI_A", "3"))
        b = os.environ["RAFIKI_B"]
        c = float(os.getenv("RAFIKI_C", "0.5"))
        d = Path(os.environ.get("RAFIKI_D", "~/x"))
        e = os.environ.get("RAFIKI_E", "0").lower() in ("1", "true")
        f = os.environ.get("RAFIKI_F", f"pw-{os.getpid()}")
        g = os.environ.get(ENV_VAR, "")
        """))
    by = env.by_knob()
    assert by["RAFIKI_A"][0].parse == "int"
    assert by["RAFIKI_A"][0].manifest_default() == "'3'"
    assert by["RAFIKI_B"][0].required
    assert by["RAFIKI_B"][0].manifest_default() == "<required>"
    assert by["RAFIKI_C"][0].parse == "float"
    assert by["RAFIKI_D"][0].parse == "path"
    assert by["RAFIKI_E"][0].parse == "flag"
    assert by["RAFIKI_F"][0].dynamic_default
    assert by["RAFIKI_F"][0].manifest_default() == "<dynamic>"
    assert "RAFIKI_INDIRECT" in by  # ENV_VAR-constant indirection


def test_env_helper_wrapped_reads_resolved_at_call_sites():
    # autoscale/health shape: module-private helpers hide the environ
    # read behind a parameter (with or without prefix concatenation);
    # constant-argument call sites must still land in the registry
    env = extract_env(_mods(m="""
        import os
        ENV_PREFIX = "RAFIKI_AS_"
        ENV_K = "RAFIKI_H_K"
        def _env_float(name, default):
            raw = os.environ.get(ENV_PREFIX + name)
            return default if raw is None else float(raw)
        def _full(name, default):
            try:
                return float(os.environ.get(name, "") or default)
            except ValueError:
                return default
        def _on(name):
            return os.environ.get(name, "1").lower() not in ("0", "off")
        def build(tick):
            a = _env_float("TICK_S", 1.0)
            b = _full(ENV_K, 50.0)
            c = _on("RAFIKI_H")
            d = _env_float(tick, 2.0)   # dynamic name: degrades silently
        """))
    by = env.by_knob()
    assert by["RAFIKI_AS_TICK_S"][0].parse == "float"
    assert by["RAFIKI_AS_TICK_S"][0].manifest_default() == "1.0"
    assert by["RAFIKI_H_K"][0].manifest_default() == "50.0"
    assert by["RAFIKI_H"][0].parse == "flag"
    assert by["RAFIKI_H"][0].manifest_default() == "'1'"  # helper-internal
    assert len(env.reads) == 3


def test_env_divergence_only_on_distinct_constant_defaults():
    env = extract_env(_mods(a="""
        import os
        x = os.environ.get("RAFIKI_K", "1")
        y = os.environ.get("RAFIKI_R", "5")
        """, b="""
        import os
        x = os.environ.get("RAFIKI_K", "4")
        y = os.environ.get("RAFIKI_R", "5")
        z = os.environ["RAFIKI_K"]          # required: can't diverge
        w = os.environ.get("RAFIKI_R", f"{1}")  # dynamic: can't diverge
        """))
    assert set(env.divergent()) == {"RAFIKI_K"}


def test_spawn_provenance_inherit_vs_explicit():
    env = extract_env(_mods(s="""
        import os, subprocess, sys
        def good():
            env = dict(os.environ)
            env["RAFIKI_EXTRA"] = "1"
            subprocess.Popen([sys.executable, "-m", "child"], env=env)
        def bad():
            env = {"PATH": "/bin", "RAFIKI_ONLY": "1"}
            subprocess.Popen([sys.executable, "-m", "child"], env=env)
        def bare():
            subprocess.run([sys.executable, "-m", "child"])
        """))
    good, bad, bare = sorted(env.spawns, key=lambda s: s.line)
    assert good.inherits_environ
    assert not bad.inherits_environ
    assert bad.explicit_keys == ("PATH", "RAFIKI_ONLY")
    assert bare.inherits_environ  # no env kwarg: child inherits


# ---------------------------------------------------------------------------
# telemetry extraction + joins
# ---------------------------------------------------------------------------


def test_telemetry_sites_dynamic_prefixes_and_collectors():
    tc = extract_telemetry(_mods(t="""
        def go(telemetry, reason, cold):
            telemetry.inc("gateway.admitted")
            telemetry.observe("train.cold_epoch_s" if cold
                              else "train.epoch_s", 1.0)
            telemetry.inc(f"gateway.shed_{reason}")
            telemetry.register_collector("goodput", lambda: {})
        """))
    names = tc.names()
    assert {"gateway.admitted", "train.cold_epoch_s",
            "train.epoch_s"} <= set(names)
    assert tc.dynamic_sites[0].prefix == "gateway.shed_"
    assert [c.name for c in tc.collectors] == ["goodput"]


def test_documented_names_brace_shorthand_and_wildcards():
    exact, wild = documented_names(textwrap.dedent("""\
        prose with `not.a.metric` backticks is ignored
        | Name | Kind | Meaning |
        |---|---|---|
        | `program_cache.{hits,misses,evictions}` | counter | x |
        | `gateway.breaker_opened` / `_half_open` / `_closed` | counter | x |
        | `trial_pack.total` / `.build` | span | x |
        | `chaos.injected` (+ `chaos.injected.<site>.<mode>`) | counter | x |
        """))
    assert {"program_cache.hits", "program_cache.misses",
            "program_cache.evictions"} <= exact
    # shorthand resolves against the row's first FULL name
    assert {"gateway.breaker_half_open", "gateway.breaker_closed"} <= exact
    assert "trial_pack.build" in exact
    assert "not.a.metric" not in exact
    assert is_documented("chaos.injected.train_epoch.delay", exact, wild)
    assert not is_documented("chaos.other", exact, wild)


def test_join_prom_golden_classification():
    tc = extract_telemetry(_mods(t="""
        def go(telemetry, reason):
            telemetry.observe("train.epoch_s", 1.0)
            telemetry.inc(f"gateway.shed_{reason}")
            telemetry.register_collector("goodput", lambda: {})
        """))
    got = join_prom_golden(textwrap.dedent("""\
        # TYPE rafiki_train_epoch_s summary
        # TYPE rafiki_goodput_goodput gauge
        # TYPE rafiki_span_trial_total summary
        # TYPE rafiki_gateway_shed_capacity counter
        # TYPE rafiki_orphan_metric counter
        """), tc)
    assert got["matched"] == ["train_epoch_s"]
    assert set(got["explained"]) == {"goodput_goodput", "span_trial_total",
                                     "gateway_shed_capacity"}
    assert got["unexplained"] == ["orphan_metric"]


# ---------------------------------------------------------------------------
# manifest determinism
# ---------------------------------------------------------------------------


def test_manifest_byte_deterministic_across_runs():
    paths = [os.path.join(REPO, "rafiki_tpu"), os.path.join(REPO, "scripts")]
    a = dump_manifest(manifest_for_paths(paths, root=REPO))
    b = dump_manifest(manifest_for_paths(paths, root=REPO))
    assert a == b
    m = json.loads(a)
    assert m["version"] == 1
    # repo-relative paths with forward slashes, however invoked
    site = next(iter(m["env"]["knobs"].values()))["sites"][0]
    assert not os.path.isabs(site) and "\\" not in site


def test_build_manifest_is_pure_and_stable_on_synthetic_tree():
    files = dict(w="""
        def go(journal):
            journal.record("mesh", "pack_formed", chip=0)
        """)
    a = dump_manifest(build_manifest(_mods(**files)))
    b = dump_manifest(build_manifest(_mods(**files)))  # fresh ASTs
    assert a == b


# ---------------------------------------------------------------------------
# RF014 — both polarities, including the doctored rename
# ---------------------------------------------------------------------------

_FIXTURE_WRITER = """
    def form_pack(journal):
        journal.record("mesh", "pack_formed", chip=0, k=4,
                       fill_ratio=1.0)
"""
_FIXTURE_READER = """
    REQUIRED_KINDS = ("mesh/pack_formed",)
    def calibrate(recs):
        for r in recs:
            if r.get("kind") == "mesh" and r.get("name") == "pack_formed":
                yield r.get("fill_ratio")
"""


def test_rf014_quiet_on_matched_fixture(tmp_path):
    paths = _write_tree(tmp_path, {"writer.py": _FIXTURE_WRITER,
                                   "reader.py": _FIXTURE_READER})
    assert _unsup(analyze_paths(paths, select=["RF014"])) == []


def test_rf014_catches_writer_side_rename_naming_both_sites(tmp_path):
    doctored = _FIXTURE_WRITER.replace("pack_formed", "pack_formedx")
    paths = _write_tree(tmp_path, {"writer.py": doctored,
                                   "reader.py": _FIXTURE_READER})
    found = _unsup(analyze_paths(paths, select=["RF014"]))
    errors = [f for f in found if f.severity == "error"]
    assert errors, "reader-side dangling expectation must be an error"
    msg = errors[0].message
    assert "mesh/pack_formed" in msg            # the kind, by name
    assert "writer.py" in msg and "renamed?" in msg  # the other site
    assert errors[0].path.endswith("reader.py")      # this site
    # and the renamed writer is now unread (warning polarity)
    assert any(f.severity == "warning" and f.path.endswith("writer.py")
               for f in found)


def test_rf014_catches_reader_side_rename_naming_both_sites(tmp_path):
    doctored = _FIXTURE_READER.replace("pack_formed", "pack_formedx")
    paths = _write_tree(tmp_path, {"writer.py": _FIXTURE_WRITER,
                                   "reader.py": doctored})
    found = _unsup(analyze_paths(paths, select=["RF014"]))
    errors = [f for f in found if f.severity == "error"]
    assert errors and errors[0].path.endswith("reader.py")
    assert "mesh/pack_formedx" in errors[0].message
    assert "mesh/pack_formed" in errors[0].message  # closest-match hint
    assert "writer.py" in errors[0].message


def test_rf014_unread_writer_is_warning_and_suppressible(tmp_path):
    files = {"writer.py": """
        def go(journal):
            journal.record("orphan", "write_only", a=1)
        """}
    [f] = _unsup(analyze_paths(_write_tree(tmp_path, files),
                               select=["RF014"]))
    assert f.severity == "warning" and "orphan/write_only" in f.message
    files_ok = {"writer.py": """
        def go(journal):
            # lint: disable=RF014 — consumed offline by ops notebooks
            journal.record("orphan", "write_only", a=1)
        """}
    assert _unsup(analyze_paths(_write_tree(tmp_path / "ok", files_ok),
                                select=["RF014"])) == []


def test_rf014_suppression_without_justification_does_not_suppress(
        tmp_path):
    files = {"writer.py": """
        def go(journal):
            journal.record("orphan", "write_only", a=1)  # lint: disable=RF014
        """}
    found = _unsup(analyze_paths(_write_tree(tmp_path, files),
                                 select=["RF014"]))
    assert found and "no justification" in found[0].message


def test_rf014_wholesale_kind_reader_covers_all_names(tmp_path):
    files = {"writer.py": """
        def go(journal):
            journal.record("chaos", "injected", site="x")
        """, "reader.py": """
        def scan(recs):
            return [r for r in recs if r.get("kind") == "chaos"]
        """}
    assert _unsup(analyze_paths(_write_tree(tmp_path, files),
                                select=["RF014"])) == []


# ---------------------------------------------------------------------------
# RF015 — both polarities + the **kwargs degrade
# ---------------------------------------------------------------------------


def test_rf015_fires_on_field_no_writer_emits(tmp_path):
    files = {"writer.py": """
        def go(journal):
            journal.record("mesh", "pack_formed", chip=0)
        """, "reader.py": _FIXTURE_READER}
    [f] = _unsup(analyze_paths(_write_tree(tmp_path, files),
                               select=["RF015"]))
    assert "fill_ratio" in f.message and f.path.endswith("reader.py")
    assert "writer.py" in f.message


def test_rf015_quiet_when_written_and_on_open_field_sets(tmp_path):
    paths = _write_tree(tmp_path, {"writer.py": _FIXTURE_WRITER,
                                   "reader.py": _FIXTURE_READER})
    assert _unsup(analyze_paths(paths, select=["RF015"])) == []
    # **kwargs writer: field set open, checker must stay silent
    files = {"writer.py": """
        def go(journal, extra):
            journal.record("mesh", "pack_formed", **extra)
        """, "reader.py": _FIXTURE_READER}
    assert _unsup(analyze_paths(_write_tree(tmp_path / "open", files),
                                select=["RF015"])) == []


def test_rf015_implicit_fields_never_flagged(tmp_path):
    files = {"writer.py": _FIXTURE_WRITER, "reader.py": """
        def scan(recs):
            for r in recs:
                if r.get("kind") == "mesh" and r.get("name") == "pack_formed":
                    yield r.get("ts"), r.get("trace_id"), r.get("pid")
        """}
    assert _unsup(analyze_paths(_write_tree(tmp_path, files),
                                select=["RF015"])) == []


# ---------------------------------------------------------------------------
# RF016 — divergence and propagation, both polarities
# ---------------------------------------------------------------------------


def test_rf016_fires_on_divergent_defaults_listing_all_sites(tmp_path):
    files = {"liba.py": """
        import os
        x = int(os.environ.get("RAFIKI_WIDTH", "1"))
        """, "libb.py": """
        import os
        x = int(os.environ.get("RAFIKI_WIDTH", "4"))
        """}
    [f] = _unsup(analyze_paths(_write_tree(tmp_path, files),
                               select=["RF016"]))
    assert "RAFIKI_WIDTH" in f.message
    assert "liba.py" in f.message and "libb.py" in f.message


def test_rf016_quiet_on_same_required_or_dynamic_defaults(tmp_path):
    files = {"liba.py": """
        import os
        x = int(os.environ.get("RAFIKI_WIDTH", "4"))
        y = os.environ["RAFIKI_OTHER"]
        """, "libb.py": """
        import os
        x = int(os.environ.get("RAFIKI_WIDTH", "4"))
        y = os.environ.get("RAFIKI_OTHER", f"{1}")
        """}
    assert _unsup(analyze_paths(_write_tree(tmp_path, files),
                                select=["RF016"])) == []


def test_rf016_unpropagated_knob_in_spawned_child(tmp_path):
    files = {"child.py": """
        import os
        WIDTH = int(os.environ.get("RAFIKI_WIDTH", "1"))
        """, "parent.py": """
        import subprocess, sys
        def spawn():
            env = {"PATH": "/bin"}
            subprocess.Popen([sys.executable, "-m", "child"], env=env)
        """}
    [f] = _unsup(analyze_paths(_write_tree(tmp_path, files),
                               select=["RF016"]))
    assert "RAFIKI_WIDTH" in f.message and f.path.endswith("parent.py")


def test_rf016_quiet_when_spawn_inherits_or_propagates(tmp_path):
    files = {"child.py": """
        import os
        WIDTH = int(os.environ.get("RAFIKI_WIDTH", "1"))
        """, "parent.py": """
        import os, subprocess, sys
        def spawn():
            env = dict(os.environ)
            subprocess.Popen([sys.executable, "-m", "child"], env=env)
        def spawn_explicit():
            env = {"PATH": "/bin", "RAFIKI_WIDTH": "4"}
            subprocess.Popen([sys.executable, "-m", "child"], env=env)
        """}
    assert _unsup(analyze_paths(_write_tree(tmp_path, files),
                                select=["RF016"])) == []
