"""hvdlint: the tier-1 static-analysis gate + analyzer self-tests.

Two halves:

* ``test_tree_is_clean_under_baseline`` IS the repo gate: every check
  against the real tree, judged against the committed baseline (new
  violations fail; stale baseline entries fail; the baseline only
  shrinks and must stay <= 10 entries).
* Planted-violation fixtures: each analyzer gets a synthetic module
  that contains exactly the defect it exists to catch, and must
  report it with the right check name, file and ident — plus a clean
  twin that must NOT fire (the false-positive pin).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.hvdlint import (CHECKS, Project, apply_baseline, gate,  # noqa: E402
                           load_baseline, run_checks)
from tools.hvdlint.check_registry_drift import doc_paths  # noqa: E402

pytestmark = pytest.mark.lint

BASELINE = os.path.join(REPO, "tools", "hvdlint", "baseline.json")


def _keys(violations):
    return {v.key for v in violations}


def _idents(violations, check=None):
    return {v.ident for v in violations
            if check is None or v.check == check}


# ---------------------------------------------------------------------------
# THE gate: the real tree, judged against the committed baseline
# ---------------------------------------------------------------------------

def test_tree_is_clean_under_baseline():
    project = Project.from_root(REPO)
    for f in project.files:
        assert f.parse_error is None, (f.relpath, f.parse_error)
    baseline = load_baseline(BASELINE)
    assert len(baseline) <= 10, \
        "baseline grew past the 10-entry budget: %r" % baseline
    result = gate(project, baseline)
    msg = "\n".join(v.render() for v in result.new)
    assert not result.new, "new hvdlint violations:\n" + msg
    assert not result.stale, \
        "stale baseline entries (violation fixed — delete them): %r" \
        % result.stale


_DOCS = ["README.md"] + sorted(
    "docs/" + fn for fn in os.listdir(os.path.join(REPO, "docs"))
    if fn.endswith(".md"))


@pytest.fixture(scope="module")
def missing_doc_paths():
    by_doc = {}
    for v in doc_paths(Project.from_root(REPO)):
        by_doc.setdefault(v.path, []).append(v.render())
    return by_doc


@pytest.mark.parametrize("doc", _DOCS)
def test_document_names_only_files_that_exist(doc, missing_doc_paths):
    """registry-drift's doc-paths direction, a case a document so that
    a failure names the document at fault."""
    assert not missing_doc_paths.get(doc), \
        "\n".join(missing_doc_paths[doc])


def test_cli_exits_zero_on_head():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.hvdlint", "--check", "all"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_unknown_check_is_usage_error():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.hvdlint", "--check", "nope"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 2
    assert "unknown check" in proc.stderr


def test_cli_fails_on_planted_tree(tmp_path):
    """End-to-end CLI: a minimal repo root with one planted violation
    must exit 1 and print the finding."""
    pkg = tmp_path / "horovod_tpu" / "common"
    pkg.mkdir(parents=True)
    (pkg / "controller_net.py").write_text(
        "def f(sock):\n    sock.settimeout(None)\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.hvdlint", "--check",
         "bounded-wait", "--root", str(tmp_path),
         "--baseline", str(tmp_path / "baseline.json")],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "settimeout-none" in proc.stdout


# ---------------------------------------------------------------------------
# baseline workflow
# ---------------------------------------------------------------------------

def test_baseline_new_grandfathered_stale_partition():
    project = Project.from_strings({
        "horovod_tpu/common/controller_net.py":
            "def f(s):\n    s.settimeout(None)\n",
    })
    violations = run_checks(project, ["bounded-wait"])
    assert violations, "the planted violation must be found"
    key = violations[0].key
    # Grandfathered: baselined key, no failure.
    res = apply_baseline(violations, [key])
    assert res.ok and res.grandfathered and not res.new
    # New: empty baseline fails.
    res = apply_baseline(violations, [])
    assert not res.ok and _keys(res.new) == {key}
    # Stale: baselined key with no matching violation fails (the
    # baseline only shrinks).
    res = apply_baseline([], [key])
    assert not res.ok and res.stale == [key]


def test_annotation_grammar_multiline_and_bare():
    src = (
        "def f(s, t, u):\n"
        "    # hvdlint: bounded-by(select polls at\n"
        "    # 0.2s so this recv cannot block)\n"
        "    s.settimeout(None)\n"
        "    # hvdlint: bounded-by()\n"
        "    t.settimeout(None)\n"
        "    u.settimeout(None)  # no annotation at all\n"
    )
    project = Project.from_strings(
        {"horovod_tpu/common/controller_net.py": src})
    violations = run_checks(project, ["bounded-wait"])
    # Annotated line 4 suppressed; empty-reason line 6 and bare line 7
    # both still fire.
    assert [v.line for v in violations] == [6, 7]


# ---------------------------------------------------------------------------
# planted fixtures, one per check
# ---------------------------------------------------------------------------

def test_bounded_wait_catches_each_construct():
    src = (
        "import queue, threading\n"
        "def f(sock, q, ev, th):\n"
        "    sock.settimeout(None)\n"
        "    sock.recv(4)\n"
        "    q.get()\n"
        "    ev.wait()\n"
        "    th.join()\n"
    )
    project = Project.from_strings(
        {"horovod_tpu/common/runtime.py": src})
    violations = run_checks(project, ["bounded-wait"])
    assert _idents(violations) == {
        "settimeout-none", "unbounded-recv", "unbounded-get",
        "unbounded-wait", "unbounded-join"}
    for v in violations:
        assert v.path == "horovod_tpu/common/runtime.py"
        assert v.line in (3, 4, 5, 6, 7)


def test_bounded_wait_clean_forms_do_not_fire():
    src = (
        "def f(sock, q, ev, th, d, parts):\n"
        "    sock.settimeout(2.0)\n"
        "    sock.recv(4)\n"          # prior settimeout in function
        "    q.get(timeout=1.0)\n"
        "    ev.wait(timeout=0.5)\n"
        "    ev.wait(5)\n"
        "    th.join(timeout=3.0)\n"
        "    d.get('key', 0)\n"       # dict get has args
        "    ','.join(parts)\n"       # str join has an arg
    )
    project = Project.from_strings(
        {"horovod_tpu/common/runtime.py": src})
    assert run_checks(project, ["bounded-wait"]) == []


def test_bounded_wait_scope_excludes_non_control_plane():
    src = "def f(sock):\n    sock.settimeout(None)\n"
    project = Project.from_strings({"horovod_tpu/models/mnist.py": src})
    assert run_checks(project, ["bounded-wait"]) == []


def test_knob_hygiene_flags_reads_not_writes():
    src = (
        "import os\n"
        "A = os.environ.get('HOROVOD_X')\n"
        "B = os.getenv('HOROVOD_Y', '1')\n"
        "C = os.environ['HOROVOD_Z']\n"
        "D = 'HOROVOD_W' in os.environ\n"
        "os.environ['HOROVOD_OK'] = '1'\n"       # write: allowed
        "E = dict(os.environ)\n"                 # passthrough: allowed
        "os.environ.update({'HOROVOD_OK': '2'})\n"
        "os.environ.pop('HOROVOD_OK', None)\n"
    )
    project = Project.from_strings({"horovod_tpu/runner/launch.py": src})
    violations = run_checks(project, ["knob-hygiene"])
    assert _idents(violations) == {"HOROVOD_X", "HOROVOD_Y",
                                   "HOROVOD_Z", "HOROVOD_W"}


def test_knob_hygiene_env_py_and_annotation_exempt():
    src = "import os\nA = os.environ.get('HOROVOD_X')\n"
    project = Project.from_strings({"horovod_tpu/common/env.py": src})
    assert run_checks(project, ["knob-hygiene"]) == []
    annotated = ("import os\n"
                 "A = os.environ.get('HOROVOD_X')  "
                 "# hvdlint: env-ok(bootstrap before env.py exists)\n")
    project = Project.from_strings(
        {"horovod_tpu/runner/launch.py": annotated})
    assert run_checks(project, ["knob-hygiene"]) == []


_HOT_HEADER = ("# hvdlint-module: hot-path\n"
               "from . import flight_recorder as _fr\n"
               "from . import failpoints as _fp\n"
               "from . import metrics\n")


def test_hot_path_gate_catches_unguarded_instrumentation():
    src = _HOT_HEADER + (
        "def handle(frame):\n"
        "    _fr.record('frame_rx', peer=1)\n"
        "    if _fp.maybe_fail('site.x') == 'drop':\n"
        "        return\n"
        "    c = metrics.counter('hvd_oops_total', 'registered hot')\n"
    )
    project = Project.from_strings(
        {"horovod_tpu/common/runtime.py": src})
    violations = run_checks(project, ["hot-path-gate"])
    assert _idents(violations) == {
        "unguarded-record", "unguarded-maybe-fail",
        "metric-registration-in-function"}


def test_hot_path_gate_else_branch_is_not_guarded():
    """A call in the ELSE branch of `if _fr.ENABLED:` runs exactly
    when disabled — the opposite of a guard — and an `and` chain only
    guards values AFTER the ENABLED check (short-circuit order)."""
    src = _HOT_HEADER + (
        "def handle(frame):\n"
        "    if _fr.ENABLED:\n"
        "        pass\n"
        "    else:\n"
        "        _fr.record('frame_rx')\n"
        "    ok = _fp.maybe_fail('s.x') == 'drop' and _fp.ENABLED\n"
    )
    project = Project.from_strings(
        {"horovod_tpu/common/runtime.py": src})
    violations = run_checks(project, ["hot-path-gate"])
    assert _idents(violations) == {"unguarded-record",
                                   "unguarded-maybe-fail"}
    assert [v.line for v in violations] == [9, 10]


def test_hot_path_gate_polices_straggler_note_sites():
    """Observability note_* feeders (the straggler collector/scorer)
    must sit behind an ENABLED check of the straggler module or an
    `is not None` guard on the object; `self.`-internal dispatch is
    out of scope."""
    src = _HOT_HEADER + (
        "from . import straggler as _sg\n"
        "def handle(col, sg, dt):\n"
        "    col.note_latency(dt)\n"                  # unguarded
        "    if _sg.ENABLED:\n"
        "        col.note_exec(dt)\n"                 # ENABLED guard
        "    if sg is not None:\n"
        "        sg.note_arrival('k', 1, dt)\n"       # None guard
        "    self_like = sg\n"
        "    if sg is not None and dt > 0:\n"
        "        self_like.note_complete('k')\n"      # BoolOp guard
        "    # hvdlint: hot-ok(cold path, loop exists iff scorer does)\n"
        "    sg.note_worker_phases({})\n"             # annotated
        "class R:\n"
        "    def on_broken(self):\n"
        "        self.note_disruption('broken')\n"    # self-dispatch
    )
    project = Project.from_strings(
        {"horovod_tpu/common/runtime.py": src})
    violations = run_checks(project, ["hot-path-gate"])
    assert _idents(violations) == {"unguarded-note"}
    assert [v.line for v in violations] == [7]


def test_hot_path_gate_guarded_and_unmarked_clean():
    guarded = _HOT_HEADER + (
        "_C = metrics.counter('hvd_ok_total', 'module scope')\n"
        "def handle(frame):\n"
        "    if _fr.ENABLED:\n"
        "        _fr.record('frame_rx', peer=1)\n"
        "    if _fp.ENABLED and _fp.maybe_fail('site.x') == 'drop':\n"
        "        return\n"
    )
    project = Project.from_strings(
        {"horovod_tpu/common/runtime.py": guarded})
    assert run_checks(project, ["hot-path-gate"]) == []
    # Same defects in an UNMARKED module: out of scope.
    unmarked = guarded.replace("# hvdlint-module: hot-path\n", "") + \
        "def cold():\n    _fr.record('x')\n"
    project = Project.from_strings(
        {"horovod_tpu/common/runtime.py": unmarked})
    assert run_checks(project, ["hot-path-gate"]) == []


def test_registry_drift_metrics_both_directions():
    src = ("from . import metrics\n"
           "_C = metrics.counter('hvd_planted_total', 'undocumented')\n")
    docs = {"docs/observability.md":
            "documents `hvd_ghost_total` which nobody registers"}
    project = Project.from_strings(
        {"horovod_tpu/common/widget.py": src}, docs)
    violations = run_checks(project, ["registry-drift"])
    idents = _idents(violations)
    assert "hvd_planted_total" in idents      # emitted, undocumented
    assert "hvd_ghost_total" in idents        # documented, dead
    by_ident = {v.ident: v for v in violations}
    assert by_ident["hvd_planted_total"].path == \
        "horovod_tpu/common/widget.py"
    assert by_ident["hvd_ghost_total"].path == "docs/observability.md"


def test_registry_drift_failpoint_sites_and_env_knobs():
    src = ("from . import failpoints as _fp\n"
           "import os\n"
           "def f():\n"
           "    if _fp.ENABLED:\n"
           "        _fp.maybe_fail('planted.site')\n"
           "    return os.environ.get('HOROVOD_PLANTED_KNOB')\n")
    docs = {
        "docs/fault_injection.md":
            "## Site catalog\n\n| `ghost.site` | gone | - |\n\n## Next\n",
        "docs/env_knobs.md": "| `HOROVOD_GHOST_KNOB` | gone |\n",
    }
    project = Project.from_strings(
        {"horovod_tpu/common/widget.py": src}, docs)
    idents = _idents(run_checks(project, ["registry-drift"]))
    assert "planted.site" in idents           # evaluated, uncataloged
    assert "ghost.site" in idents             # cataloged, dead
    assert "HOROVOD_PLANTED_KNOB" in idents   # read, undocumented
    assert "HOROVOD_GHOST_KNOB" in idents     # cataloged, dead


def test_registry_drift_doc_paths():
    docs = {"README.md": (
        "`tools/ghost.py` and `python ghost_tool.py --smoke` are gone;\n"
        "```\npython tools/fenced_ghost.py --x\n```\n"
        "`horovod_tpu/common/widget.py:12-40`, `common/widget.py`,\n"
        "`widget.py`, `horovod_tpu/common/` and `docs/guide.md` exist;\n"
        "`tests/test_widget.py::test_it` does not; out of scope:\n"
        "`horovod/common/ops.cc`, `examples/pytorch/mnist.py`,\n"
        "`docs/benchmarks.rst`, `tests/test_*.py`, `<file>.py`,\n"
        "`https://example.org/x.py`, `hvd.init()`.\n"),
        "docs/guide.md": "nothing to see"}
    project = Project.from_strings(
        {"horovod_tpu/common/widget.py": "x = 1\n"}, docs)
    found = doc_paths(project)
    assert _idents(found) == {"tools/ghost.py", "ghost_tool.py",
                              "tools/fenced_ghost.py",
                              "tests/test_widget.py"}
    assert {v.path for v in found} == {"README.md"}
    assert set(_keys(found)) <= set(_keys(
        run_checks(project, ["registry-drift"])))


def test_frame_parity_unhandled_kind_and_oos_tables():
    controller = (
        "_MAGIC_REQ = b'RQ'\n"
        "_MAGIC_HB = b'HB'\n"
        "_MAGIC_METRICS_REQ = b'MQ'\n"
        "_MAGIC_METRICS_REP = b'MR'\n"
        "_MAGIC_ROGUE = b'ZZ'\n"
        "_OOS_DOWN = (_MAGIC_HB,)\n"          # wrong: MQ missing
        "_OOS_UP = (_MAGIC_HB, _MAGIC_METRICS_REP)\n"
        "def send(sock):\n"
        "    _send_frame(sock, _MAGIC_ROGUE, b'')\n"
        "def recv(magic):\n"
        "    if magic == _MAGIC_REQ:\n"
        "        return True\n"
        "    if magic in _OOS_UP:\n"
        "        return True\n"
    )
    relay = (
        "MAGIC_METRICS_AGG = b'MA'\n"
        "def on_frame(magic):\n"
        "    if magic == b'HB':\n"
        "        return True\n"
        "    if magic == b'MQ':\n"
        "        return True\n"
        "    if magic == b'MR':\n"
        "        return True\n"
        # MA deliberately NOT dispatched
    )
    project = Project.from_strings({
        "horovod_tpu/common/controller_net.py": controller,
        "horovod_tpu/common/relay.py": relay,
    })
    idents = _idents(run_checks(project, ["frame-parity"]))
    assert "unhandled-kind-ZZ" in idents
    assert "oos-table-_OOS_DOWN" in idents
    assert "oos-relay-MA" in idents
    # The correctly-classified table did not fire.
    assert "oos-table-_OOS_UP" not in idents


def test_every_check_is_exercised_by_a_fixture():
    """Meta: the suite above plants at least one violation per
    registered check (so adding a check without a fixture fails)."""
    assert set(CHECKS) == {"bounded-wait", "knob-hygiene",
                           "hot-path-gate", "registry-drift",
                           "frame-parity"}


def test_baseline_file_is_valid_json_with_known_shape():
    with open(BASELINE) as fh:
        data = json.load(fh)
    assert set(data) == {"grandfathered"}
    assert isinstance(data["grandfathered"], list)
