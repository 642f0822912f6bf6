"""Tier-1 coverage for the static-analysis layer (ISSUE 7).

Runs the cross-surface invariant linter (tools/check_invariants.py)
against the real tree — so any enum/ABI/failpoint/metric/doc drift
fails the ordinary pytest suite, not just run_test.sh — and proves the
linter actually BITES: each seeded mutation below (remove an op from
one side, rename a metric, grow the ABI surface without updating the
golden, add an undocumented failpoint, break a status mirror, strip a
tsan.supp citation) must flip its exit code to non-zero with the
matching violation named.

The mutation tests copy the parsed surfaces into a tmp tree and run the
linter with --root there; the real tree is never touched.
"""

import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINTER = os.path.join(REPO, "tools", "check_invariants.py")

# Everything the linter parses, relative to the root it is given.
SURFACE_FILES = [
    "native/tsan.supp",
    "infinistore_tpu/_native.py",
    "infinistore_tpu/server.py",
    "docs/api.md",
    "docs/design.md",
    "tools/abi_surface.json",
]


def run_linter(root=None):
    cmd = [sys.executable, LINTER]
    if root:
        cmd += ["--root", root]
    return subprocess.run(cmd, capture_output=True, text=True)


@pytest.fixture()
def tree(tmp_path):
    """A minimal copy of every linted surface, safe to mutate."""
    root = tmp_path / "tree"
    src = root / "native" / "src"
    src.mkdir(parents=True)
    for fn in os.listdir(os.path.join(REPO, "native", "src")):
        if fn.endswith((".cc", ".h")):
            shutil.copy(os.path.join(REPO, "native", "src", fn), src / fn)
    for rel in SURFACE_FILES:
        dst = root / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO, rel), dst)
    return root


def mutate(root, rel, old, new, count=1):
    p = os.path.join(root, rel)
    with open(p, encoding="utf-8") as f:
        text = f.read()
    assert old in text, f"mutation anchor {old!r} missing from {rel}"
    with open(p, "w", encoding="utf-8") as f:
        f.write(text.replace(old, new, count))


def test_linter_clean_on_tree():
    r = run_linter()
    assert r.returncode == 0, r.stdout + r.stderr
    assert "check_invariants: OK" in r.stdout


def test_linter_clean_on_copied_tree(tree):
    # The fixture copy itself must lint clean, or every mutation test
    # below would be asserting against pre-existing noise.
    r = run_linter(str(tree))
    assert r.returncode == 0, r.stdout + r.stderr


def test_removed_op_fails(tree):
    # Remove OP_PREFETCH from common.h only: the wire surface no longer
    # matches the pinned golden.
    mutate(tree, "native/src/common.h", "    OP_PREFETCH = 20,", "")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "'ops' drifted" in r.stderr


def test_renamed_metric_fails(tree):
    # Rename a stats key in the native emitter only: the Prometheus
    # renderer still reads the old name.
    mutate(tree, "native/src/server.cc", '\\"hard_stalls\\":',
           '\\"hard_stallz\\":')
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "metrics:" in r.stderr and "hard_stalls" in r.stderr


def test_new_export_without_abi_bump_fails(tree):
    # Grow the C ABI on both language sides but skip the golden update
    # and the ist_abi_version() bump — exactly the "silent surface
    # growth" the golden exists to catch.
    mutate(tree, "native/src/capi.cc", 'extern "C" {',
           'extern "C" {\nuint32_t ist_totally_new(void* h) {\n'
           '    (void)h;\n    return 0;\n}\n')
    mutate(tree, "infinistore_tpu/_native.py",
           '("ist_abi_version", c.c_uint32, []),',
           '("ist_abi_version", c.c_uint32, []),\n'
           '        ("ist_totally_new", c.c_uint32, [c.c_void_p]),')
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "'exports' drifted" in r.stderr
    assert "bump ist_abi_version" in r.stderr


def test_undeclared_export_fails(tree):
    # Export with no ctypes declaration: dead (or worse, untested) ABI.
    mutate(tree, "native/src/capi.cc", 'extern "C" {',
           'extern "C" {\nuint32_t ist_totally_new(void* h) {\n'
           '    (void)h;\n    return 0;\n}\n')
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "no ctypes declaration" in r.stderr


def test_status_value_mismatch_fails(tree):
    mutate(tree, "infinistore_tpu/_native.py", "BUSY = 429", "BUSY = 430")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "status-mirror" in r.stderr and "BUSY" in r.stderr


def test_undocumented_failpoint_fails(tree):
    # Compile in a new inject point without cataloging/documenting it.
    mutate(tree, "native/src/disk_tier.cc",
           'IST_FAILPOINT("disk.reserve")',
           '(IST_FAILPOINT("disk.fsync"), IST_FAILPOINT("disk.reserve"))',
           count=1)
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "disk.fsync" in r.stderr
    assert "catalog" in r.stderr or "undocumented" in r.stderr


def test_engine_stat_rename_fails(tree):
    # Engine-knob drift (ISSUE 8): rename the uring counter in the
    # native emitter only (both the aggregate and the per-worker
    # entry); the Prometheus renderer still reads uring_zc_sends.
    mutate(tree, "native/src/server.cc", '\\"uring_zc_sends\\":',
           '\\"uring_zc_send_ops\\":', count=8)
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "metrics:" in r.stderr and "uring_zc_sends" in r.stderr


def test_engine_failpoint_catalog_drift_fails(tree):
    # The engine.uring_setup probe failpoint stays compiled in
    # (engine_uring.cc) while its catalog row is renamed away: the
    # linter must flag the missing catalog entry.
    mutate(tree, "native/src/failpoint.h", "//   engine.uring_setup",
           "//   engine.uring_probe")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "engine.uring_setup" in r.stderr
    assert "catalog" in r.stderr


def test_uncited_suppression_fails(tree):
    # Every tsan.supp entry must carry a live `# cite: file:line`.
    mutate(tree, "native/tsan.supp",
           "# cite: native/src/client.cc:1560 "
           "(handle_readable: rpc-response fill)\n", "")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "tsan-supp" in r.stderr and "cite" in r.stderr


def test_appended_uncited_suppression_fails(tree):
    # Cites must not leak across block boundaries: a new family
    # appended after a blank line + its own (cite-less) header comment
    # must fail even though earlier blocks are fully cited.
    p = os.path.join(tree, "native/tsan.supp")
    with open(p, "a", encoding="utf-8") as f:
        f.write("\n# a new FP family, not yet anchored\n"
                "mutex:istpu::Server::stop\n")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "tsan-supp" in r.stderr and "cite" in r.stderr


def test_removed_op_doc_row_fails(tree):
    # OP_COMMIT's doc row must be required even though OP_COMMIT_BATCH
    # (a superstring) stays documented — word-boundary, not substring.
    mutate(tree, "docs/api.md", "| `OP_COMMIT` | 5 |", "| (redacted) | 5 |")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "OP_COMMIT" in r.stderr and "wire table" in r.stderr


def test_unreachable_suppression_fails(tree):
    # A suppression whose symbol vanished from native/src must be pruned.
    mutate(tree, "native/tsan.supp",
           "race:istpu::Connection::handle_readable",
           "race:istpu::Connection::handle_readable_gone")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "prune" in r.stderr


def test_uncataloged_event_emit_fails(tree):
    # Flight-recorder drift, side 1 (ISSUE 10): an events_emit call
    # site whose id has no IST_EVENT_CATALOG row — an event the drain
    # would render as "?" and the docs never explain.
    mutate(tree, "native/src/server.cc", "namespace istpu {",
           "namespace istpu {\n"
           "static inline void _bogus_emit() {\n"
           "    events_emit(EV_BOGUS_EVENT, 0, 0);\n"
           "}\n", count=1)
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "events:" in r.stderr and "EV_BOGUS_EVENT" in r.stderr
    assert "no\n" not in r.stdout  # sanity: failure came from stderr


def test_stale_event_catalog_row_fails(tree):
    # Flight-recorder drift, side 2: a catalog row with no emit site —
    # dead surface that would rot in the docs and the golden.
    mutate(tree, "native/src/events.h",
           'X(EV_BUNDLE_CAPTURED, "watchdog.bundle", SEV_INFO)',
           'X(EV_BUNDLE_CAPTURED, "watchdog.bundle", SEV_INFO) \\\n'
           '    X(EV_GHOST_ROW, "ghost.row", SEV_INFO)')
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "stale catalog row" in r.stderr and "EV_GHOST_ROW" in r.stderr


def test_undocumented_endpoint_fails(tree):
    # A control-plane endpoint the docs do not mention.
    mutate(tree, "infinistore_tpu/server.py",
           'self.path == "/kvmap_len"',
           'self.path == "/kvmap_len_v2"')
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "/kvmap_len_v2" in r.stderr


def test_dropped_slo_endpoint_fails_golden(tree):
    # ISSUE 11 seeded mutation: silently deleting the /slo endpoint
    # from the control plane must fail the golden's new `endpoints`
    # section — dashboards depend on it exactly like bindings depend
    # on exports. (Renaming would ALSO trip the undocumented-endpoint
    # check; deletion only the golden catches.)
    mutate(tree, "infinistore_tpu/server.py",
           'elif self.path == "/slo":',
           'elif self.path == "/slo_disabled_never_matches":')
    # Keep the docs check quiet so the failure isolates the golden
    # endpoint pin (the mutated path is undocumented too).
    mutate(tree, "docs/api.md", "`GET /slo`",
           "`GET /slo` `/slo_disabled_never_matches`")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "'endpoints' drifted" in r.stderr


def test_dropped_workload_endpoint_fails_golden(tree):
    # ISSUE 13 seeded mutation: silently deleting the /workload
    # endpoint from the control plane must fail the golden's
    # `endpoints` pin — the MRC/WSS dashboard depends on it exactly
    # like bindings depend on exports. (The doc edit keeps the
    # undocumented-endpoint check quiet so the failure isolates the
    # golden pin, same shape as the /slo mutation above.)
    mutate(tree, "infinistore_tpu/server.py",
           'elif self.path == "/workload":',
           'elif self.path == "/workload_disabled_never_matches":')
    mutate(tree, "docs/api.md", "`GET /workload`",
           "`GET /workload` `/workload_disabled_never_matches`")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "'endpoints' drifted" in r.stderr


def test_thrash_event_catalog_pin_bites(tree):
    # ISSUE 13 seeded mutation: renaming the watchdog.thrash verdict's
    # emit id (server.cc) without touching the events.h catalog must
    # fail BOTH drift directions — the new id is emitted but
    # uncataloged (the drain would render "?"), the old catalog row is
    # stale — so the thrash verdict can never silently detach from its
    # catalog row (and hence from the docs table) after a refactor.
    mutate(tree, "native/src/server.cc",
           "events_emit(EV_WATCHDOG_THRASH,",
           "events_emit(EV_WATCHDOG_THRASHING,")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "EV_WATCHDOG_THRASHING" in r.stderr  # emitted, uncataloged
    assert "EV_WATCHDOG_THRASH" in r.stderr     # stale catalog row
    assert "stale catalog row" in r.stderr


def test_fabric_failpoint_catalog_pin_bites(tree):
    # ISSUE 12 seeded mutation: renaming the fabric doorbell failpoint
    # at its call site (engine_fabric.cc) without touching the
    # failpoint.h catalog must fail BOTH drift directions — the new
    # name is compiled in but uncataloged (an armable-but-invisible
    # point), the old catalog row is stale — and the golden's pinned
    # `failpoints` section drifts too. This is the pin that keeps
    # chaos specs (`fabric.doorbell=...`) from silently arming
    # nothing after a refactor.
    mutate(tree, "native/src/engine_fabric.cc",
           'IST_FAILPOINT("fabric.doorbell")',
           'IST_FAILPOINT("fabric.bell")')
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "fabric.bell" in r.stderr  # compiled-in but uncataloged
    assert "fabric.doorbell" in r.stderr  # stale catalog row


def test_conn_shed_event_catalog_pin_bites(tree):
    # ISSUE 18 seeded mutation: renaming the shed path's emit id
    # (server.cc) without touching the events.h catalog must fail BOTH
    # drift directions — the new id is emitted but uncataloged, the old
    # catalog row is stale — so the accept path's shed policy can never
    # silently detach from its catalog row (and hence the docs table
    # and the golden's pinned `events` section) after a refactor.
    mutate(tree, "native/src/server.cc",
           "events_emit(EV_CONN_SHED,",
           "events_emit(EV_CONN_SHEDDED,")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "EV_CONN_SHEDDED" in r.stderr  # emitted, uncataloged
    assert "EV_CONN_SHED" in r.stderr     # stale catalog row
    assert "stale catalog row" in r.stderr


def test_conn_shed_failpoint_catalog_pin_bites(tree):
    # ISSUE 18 seeded mutation: renaming the shed failpoint at its call
    # site (server.cc) without touching the failpoint catalog must fail
    # both directions, exactly like the fabric.doorbell pin above —
    # this is what keeps the CI chaos step's `conn.shed=...` specs from
    # silently arming nothing.
    mutate(tree, "native/src/server.cc",
           'IST_FAILPOINT("conn.shed")',
           'IST_FAILPOINT("conn.drop")')
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "conn.drop" in r.stderr  # compiled-in but uncataloged
    assert "conn.shed" in r.stderr  # stale catalog row


def test_ring_detach_event_catalog_pin_bites(tree):
    # ISSUE 18 seeded mutation: the ring-pool LRU reclaim's detach
    # event (engine_fabric.cc) is the only externally visible record
    # that a writer's commit ring was taken away — renaming its emit id
    # without the catalog must fail both drift directions so the
    # detach protocol can never go dark.
    mutate(tree, "native/src/engine_fabric.cc",
           "events_emit(EV_FABRIC_RING_DETACH,",
           "events_emit(EV_FABRIC_RING_DROP,")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "EV_FABRIC_RING_DROP" in r.stderr    # emitted, uncataloged
    assert "EV_FABRIC_RING_DETACH" in r.stderr  # stale catalog row


def test_dropped_directory_endpoint_fails_golden(tree):
    # ISSUE 14 seeded mutation: silently deleting the /directory
    # endpoint must fail the golden's `endpoints` pin — every cluster
    # client's epoch refresh and the coordinator's push path depend on
    # it. The handler string appears in BOTH do_GET and do_POST, so
    # the mutation hits every occurrence (one survivor would keep the
    # endpoint in the parsed set and hide the drift).
    mutate(tree, "infinistore_tpu/server.py",
           'self.path == "/directory":',
           'self.path == "/directory_disabled_never_matches":',
           count=2)
    # Keep the docs check quiet so the failure isolates the golden pin.
    mutate(tree, "docs/api.md", "`GET /directory`",
           "`GET /directory` `/directory_disabled_never_matches`")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "'endpoints' drifted" in r.stderr


def test_added_directory_endpoint_fails_golden(tree):
    # ...and the REVERSE drift direction: a grown endpoint surface
    # (documented, so only the golden can catch it) must also fail
    # until the golden is regenerated — surface growth needs the same
    # deliberate golden+ABI step as surface loss.
    mutate(tree, "infinistore_tpu/server.py",
           'elif self.path == "/directory":',
           'elif self.path == "/directory2":\n'
           '                self._send(200, {})\n'
           '            elif self.path == "/directory":')
    mutate(tree, "docs/api.md", "`GET /directory`",
           "`GET /directory` `/directory2`")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "'endpoints' drifted" in r.stderr


def test_migration_event_catalog_pin_bites(tree):
    # ISSUE 14 seeded mutation: renaming the watchdog.migration
    # verdict's emit id (server.cc migration_trip) without touching
    # the events.h catalog must fail BOTH drift directions — the new
    # id is emitted but uncataloged, the old catalog row is stale —
    # so the migration verdict can never silently detach from its
    # catalog row (and the docs table) after a refactor.
    mutate(tree, "native/src/server.cc",
           "events_emit(EV_WATCHDOG_MIGRATION,",
           "events_emit(EV_WATCHDOG_MIGRATING,")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "EV_WATCHDOG_MIGRATING" in r.stderr  # emitted, uncataloged
    assert "EV_WATCHDOG_MIGRATION" in r.stderr  # stale catalog row
    assert "stale catalog row" in r.stderr


def test_cluster_failpoint_catalog_pin_bites(tree):
    # ISSUE 14 seeded mutation: renaming a cluster failpoint at its
    # eval site (capi.cc ist_cluster_failpoint) without the
    # failpoint.h catalog must fail both directions, exactly like the
    # fabric pin above — a chaos spec (`cluster.migrate_export=...`)
    # must never silently arm nothing after a refactor.
    mutate(tree, "native/src/capi.cc",
           'IST_FAILPOINT("cluster.migrate_export")',
           'IST_FAILPOINT("cluster.range_export")')
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "cluster.range_export" in r.stderr  # compiled, uncataloged
    assert "cluster.migrate_export" in r.stderr  # stale catalog row


def test_dropped_cluster_status_endpoint_fails_golden(tree):
    # ISSUE 15 seeded mutation: silently deleting /cluster/status must
    # fail the golden's `endpoints` pin — istpu_top --cluster,
    # istpu_trace --cluster discovery and every fleet dashboard read
    # it. Docs patched so the failure isolates the golden pin.
    mutate(tree, "infinistore_tpu/server.py",
           'self.path == "/cluster/status":',
           'self.path == "/cluster/status_disabled":')
    mutate(tree, "docs/api.md", "`GET /cluster/status`",
           "`GET /cluster/status` `/cluster/status_disabled`")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "'endpoints' drifted" in r.stderr


def test_cluster_trip_event_catalog_pin_bites(tree):
    # ISSUE 15 seeded mutation: renaming the replica-divergence
    # verdict's emit id (server.cc cluster_trip) without the events.h
    # catalog must fail BOTH drift directions, like the migration pin.
    mutate(tree, "native/src/server.cc",
           "events_emit(EV_WATCHDOG_DIVERGENCE,",
           "events_emit(EV_WATCHDOG_DIVERGED,")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "EV_WATCHDOG_DIVERGED" in r.stderr   # emitted, uncataloged
    assert "EV_WATCHDOG_DIVERGENCE" in r.stderr  # stale catalog row
    assert "stale catalog row" in r.stderr


def test_wrong_epoch_stats_key_rename_fails(tree):
    # ISSUE 15 seeded mutation: renaming the stats_json cluster
    # section's wrong_epoch_rejections key must fail the golden's
    # stats_keys pin (the key set GREW with the new spelling) — the
    # epoch-propagation telemetry must never silently go dark under a
    # refactor. (The anchor's closing `}` scopes the mutation to the
    # stats_json copy of the key, not cluster_json's.)
    mutate(tree, "native/src/server.cc",
           '"\\"wrong_epoch_rejections\\": %llu, "\n'
           '                 "\\"adopt_unix_us\\": %lld}",',
           '"\\"wrong_epoch_refusals\\": %llu, "\n'
           '                 "\\"adopt_unix_us\\": %lld}",')
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "'stats_keys' drifted" in r.stderr


def test_removed_put_hash_op_fails(tree):
    # ISSUE 16 seeded mutation, op pin direction 1: deleting the
    # OP_PUT_HASH wire op from common.h must fail the golden's `ops`
    # section — a v16 client's hash-first put would hit UNSUPPORTED and
    # dedup would silently degrade to full-payload transfer.
    mutate(tree, "native/src/common.h", "    OP_PUT_HASH = 24,", "")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "'ops' drifted" in r.stderr


def test_removed_put_hash_doc_row_fails(tree):
    # ISSUE 16 seeded mutation, op pin direction 2: the op exists in
    # code but every api.md mention vanished (the wire-table row AND
    # the ClientConfig use_dedup cross-reference — the doc check is
    # word-boundary over the whole file, so both must go to trip it;
    # the suffixed spelling fails the \b match by design).
    mutate(tree, "docs/api.md", "OP_PUT_HASH", "OP_PUT_HASH_REDACTED",
           count=2)
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "OP_PUT_HASH" in r.stderr and "wire table" in r.stderr


def test_dedup_hits_stats_key_rename_fails(tree):
    # ISSUE 16 seeded mutation, stats pin both directions at once:
    # renaming the stats_json dedup section's dedup_hits key removes
    # the pinned spelling AND adds an unpinned one — the golden's
    # stats_keys section must catch either, so the capacity-multiplier
    # telemetry can never silently go dark under a refactor. (The
    # colon-anchored spelling scopes the mutation to the stats emitter,
    # not the history ring's dedup_hits_delta.)
    mutate(tree, "native/src/server.cc", '\\"dedup_hits\\":',
           '\\"dedup_hitz\\":')
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "'stats_keys' drifted" in r.stderr


def test_added_dedup_stats_key_fails_golden(tree):
    # ISSUE 16 seeded mutation, stats pin grow direction in isolation:
    # a brand-new dedup stats key without a golden regen is silent
    # surface growth, exactly like an export without an ABI bump.
    mutate(tree, "native/src/server.cc",
           '"\\"dedup_hits\\": %llu, "',
           '"\\"dedup_hits\\": %llu, \\"dedup_bogus_total\\": 0, "')
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "'stats_keys' drifted" in r.stderr


def test_iosched_decision_event_catalog_pin_bites(tree):
    # ISSUE 17 seeded mutation: renaming the closed-loop controller's
    # decision event at its emit site (server.cc iosched_tick) without
    # touching the events.h catalog must fail BOTH drift directions —
    # the new id is emitted but uncataloged, the old catalog row is
    # stale — so "every autotune decision is a flight-recorder event"
    # can never silently stop being true after a refactor.
    mutate(tree, "native/src/server.cc",
           "events_emit(EV_IOSCHED_DECISION,",
           "events_emit(EV_IOSCHED_DECIDED,")
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "EV_IOSCHED_DECIDED" in r.stderr  # emitted, uncataloged
    assert "EV_IOSCHED_DECISION" in r.stderr  # stale catalog row
    assert "stale catalog row" in r.stderr


def test_iosched_stats_key_rename_fails(tree):
    # ISSUE 17 seeded mutation: renaming the iosched section's served
    # counter in stats_json must fail the golden's stats_keys pin in
    # both directions at once (old key gone, new key unpinned) — the
    # scheduler telemetry /metrics and istpu_top read must never
    # silently go dark under a refactor.
    mutate(tree, "native/src/server.cc",
           '"\\"iosched_served\\": %llu, "',
           '"\\"iosched_grants\\": %llu, "')
    r = run_linter(str(tree))
    assert r.returncode != 0
    assert "'stats_keys' drifted" in r.stderr


# ---------------------------------------------------------------------------
# Documents name files that exist: a deletion (PR 28 took out the old
# root benchmark script and everything only it read) must not leave
# instructions that point at a file the tree no longer has.
# ---------------------------------------------------------------------------

DOCUMENTS = [
    "README.md",
    "docs/api.md",
    "docs/design.md",
    "PARITY.md",
    ".github/workflows/ci.yml",
    ".claude/skills/verify/SKILL.md",
]

# A repo-relative *.py / *.sh path: no leading "/" (absolute paths and
# the reference's own tree are not ours to check), no glob or
# placeholder characters.
_SCRIPT_PATH = re.compile(
    r"(?<![\w./*<{-])((?:\./)?[\w.-]+(?:/[\w.-]+)*\.(?:py|sh))(?![\w*-])")


def _script_paths_named(text, markdown):
    """Paths a document names in a command or in backticks: for
    markdown, fenced blocks and `code spans`; for the workflow file,
    every line (its prose is comments on commands)."""
    if not markdown:
        return set(_SCRIPT_PATH.findall(text))
    named, fenced = set(), False
    for line in text.split("\n"):
        if line.lstrip().startswith("```"):
            fenced = not fenced
            continue
        for span in [line] if fenced else re.findall(r"`([^`]*)`", line):
            named.update(_SCRIPT_PATH.findall(span))
    return named


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_files_in_the_tree(doc):
    """Every *.py / *.sh path the document names resolves from the
    repository root or from infinistore_tpu/ (how the documents
    abbreviate `models/llama.py`)."""
    with open(os.path.join(REPO, doc)) as f:
        named = _script_paths_named(f.read(), doc.endswith(".md"))
    assert named, f"{doc} names no script at all: the extractor is broken"
    missing = sorted(
        p for p in named
        if not any(os.path.exists(os.path.join(REPO, base, p))
                   for base in ("", "infinistore_tpu")))
    assert not missing, f"{doc} names files the tree does not have: {missing}"


def test_make_analyze_exits_zero():
    # With clang installed this is the -Wthread-safety -Werror proof
    # pass; without it the target reports the skip and still exits 0 —
    # either way `make analyze` must never break a checkout.
    r = subprocess.run(
        ["make", "-C", os.path.join(REPO, "native"), "analyze"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


def test_annotation_macros_are_noops_under_gcc():
    # The annotation layer must vanish under non-clang compilers: the
    # release .so is built by g++ and must not change shape. Pin the
    # guard so a future edit cannot accidentally make the macros
    # unconditional.
    path = os.path.join(REPO, "native", "src", "thread_annotations.h")
    with open(path, encoding="utf-8") as f:
        text = f.read()
    assert "__clang__" in text
    assert "#define ISTPU_TSA(x)  // no-op" in text


def test_lock_rank_gated_to_sanitizer_builds():
    # The runtime checker must stay out of release builds (hot path is
    # contractually byte-identical): the Makefile compiles it only via
    # SAN_FLAGS, and lock_rank.h compiles to the thin shell without it.
    mk = open(os.path.join(REPO, "native", "Makefile"),
              encoding="utf-8").read()
    assert "-DISTPU_LOCK_RANK" in mk
    assert "-DISTPU_LOCK_RANK" in [
        line for line in mk.splitlines() if "SAN_FLAGS" in line and
        ":=" in line][0]
    cxxflags = [line for line in mk.splitlines()
                if line.startswith("CXXFLAGS")][0]
    assert "ISTPU_LOCK_RANK" not in cxxflags
