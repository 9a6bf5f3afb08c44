# Build/test entry points, mirroring the reference's workflow
# (reference Makefile:53-66: `make test` runs the suite, `make check`
# runs lint, plus a coverage target).  Everything here is stdlib +
# baked-in tooling only.

PYTHON ?= python3
LINT_TARGETS = zkstream_tpu tests tools chip_smoke.py \
    __graft_entry__.py

.PHONY: all test check analyze native loadgen asan ubsan \
    sanitize chaos chaos-ensemble obs durability election linearize \
    reconfig overload cache timeline coverage clean

all: check test

test: native
	$(PYTHON) -m pytest tests/ -q

# Bounded seeded chaos campaign (<= 60 s): fault-injection schedules
# + the resilience tests (deadlines, degraded mode, member kills).
# Same invariants as the full tier-1 campaign, smaller slice; rerun
# any failing seed with `python -m zkstream_tpu chaos --seed N`.
# Scale with ZKSTREAM_CHAOS_SCHEDULES / ZKSTREAM_CHAOS_SEED.
chaos:
	ZKSTREAM_CHAOS_SCHEDULES=$${ZKSTREAM_CHAOS_SCHEDULES:-60} \
	    $(PYTHON) -m pytest tests/test_chaos.py -q -m 'not slow'

# Ensemble-tier chaos, bounded slice: member kills/restarts,
# replication partitions, session migration + the history-checked
# invariant engine (io/invariants.py).  `-m 'not slow'` keeps the
# full >=100-schedule campaign out of this target (it runs under the
# slow marker: pytest tests/test_chaos_ensemble.py -m slow).  Rerun a
# failing seed with `python -m zkstream_tpu chaos --tier ensemble
# --seed N`; scale with ZKSTREAM_CHAOS_ENS_TIER1 / _SEED.
chaos-ensemble:
	$(PYTHON) -m pytest tests/test_chaos_ensemble.py -q -m 'not slow'

# Durability plane (server/persist.py; README "Durability"): the WAL
# unit corpus (torn-write truncation at every byte offset, bit-flip
# CRC rejection, rotation/snapshot recovery, sync policies), the
# ensemble tier-1 slice — whose every schedule now ends with a
# full-ensemble SIGKILL crash image and a restart-from-disk recovery
# checked by the invariant engine (invariant 6, io/invariants.py) —
# plus the PR-12 scenario suite: torn-multi all-or-nothing recovery
# at every byte offset, full-restart-with-live-ephemerals (durable
# sessions), the quorum-gate units, and the MULTI pillar; the
# leader-killed-after-ack scenario runs on the OS-process tier
# (tests/test_process_ensemble.py / chaos --tier process).
durability:
	$(PYTHON) -m pytest tests/test_wal.py tests/test_chaos_ensemble.py \
	    tests/test_durability_scenarios.py tests/test_multi.py \
	    -q -m 'not slow'
	$(PYTHON) -m pytest tests/test_process_ensemble.py -q \
	    -k 'election_kill_loop'

# Coordination plane (server/election.py; README "Failure
# semantics"): the vote rule + invariant 7 units, the in-process
# coordinator suite (heartbeat detection, quorum gate, deposed-member
# fencing, pool re-resolution), the forced-election ensemble chaos
# slice, and the OS-process tier — elected-leader kill loops plus
# full-ensemble SIGKILL -> election from recovered WALs, 2
# generations deep.  Rerun any seed with `python -m zkstream_tpu
# chaos --tier ensemble --elections 2 --seed N` (or --tier process).
election:
	$(PYTHON) -m pytest tests/test_election.py -q
	$(PYTHON) -m pytest tests/test_chaos_ensemble.py -q \
	    -k 'election' -m 'not slow'
	$(PYTHON) -m pytest tests/test_process_ensemble.py -q \
	    -k 'election or member_worker'

# Dynamic-membership suite (README "Dynamic membership"): the
# reconfig unit/property tests — joint-majority arithmetic, removed-
# voter fencing, observer join under write load (byte-identical
# replica), WAL-recovered in-progress reconfig, resolver rebalance —
# plus reconfig-enabled chaos slices on both tiers (per-era voter
# replaces and a full-ensemble SIGKILL mid-joint-window on the
# OS-process tier).  Rerun any seed with `python -m zkstream_tpu
# chaos --tier ensemble --reconfig --seed N` (or --tier process).
reconfig:
	$(PYTHON) -m pytest tests/test_reconfig.py -q -m 'not slow'

# Overload plane (io/overload.py; README "Overload plane"): admission
# control, the inbound frame cap, rx/tx backpressure, slow-consumer
# eviction, the global THROTTLED write bounce — units + e2e + the
# tier-1 chaos slices with forced overload bursts, then the full
# 120-schedule acceptance campaign (the slow marker).  Rerun a
# failing campaign seed with `python -m zkstream_tpu chaos --tier
# ensemble --overload --seed N`; scale with
# ZKSTREAM_OVERLOAD_SCHEDULES / ZKSTREAM_CHAOS_SEED.
overload:
	$(PYTHON) -m pytest tests/test_overload.py -q -m 'not slow'
	$(PYTHON) -m pytest tests/test_overload.py -q -m slow \
	    -k overload_campaign

# Client cache plane (io/cache.py; README "Client cache plane"):
# persistent / persistent-recursive watch semantics (ADD_WATCH,
# SET_WATCHES2 replay), the watch-backed cache units — serve gate,
# fill gate, invalidation, knob resolution, metrics — plus the
# cached-client chaos slices on both tiers (every cached read rides
# the same check_session_reads invariant as a wire read).  Rerun a
# failing seed with `python -m zkstream_tpu chaos --tier ensemble
# --cached --seed N` (or --tier process).  The full 120-schedule
# cached campaign is the slow marker (test_cached_campaign_full).
cache:
	$(PYTHON) -m pytest tests/test_cache.py -q
	$(PYTHON) -m pytest tests/test_chaos_ensemble.py -q \
	    -k 'cached' -m 'not slow'
	$(PYTHON) -m pytest tests/test_process_ensemble.py -q \
	    -k 'cached'

# Observability suite: metrics (counters/gauges/histograms +
# exposition), causal tracing (client spans + member rings + the
# zxid-merged timeline), the tick ledger, the four-letter admin
# words (ruok/mntr/stat/srvr/trce), and the black-box plane (crash-
# durable flight recorder + slow-op digest + `top` collector) — see
# README "Observability".
obs:
	$(PYTHON) -m pytest tests/test_metrics.py tests/test_trace.py \
	    tests/test_admin_words.py tests/test_blackbox.py -q

# Causal-tracing demo: run one traced write through an in-process
# 3-member ensemble (WAL on, watch armed) and print the merged
# zxid-ordered timeline — client submit, leader commit + WAL append +
# shared group-fsync span, follower applies, fan-out delivery (README
# "Causal tracing").  `--live` against a running ensemble:
# python -m zkstream_tpu --server h:p,h:p timeline --live
timeline:
	$(PYTHON) -m zkstream_tpu timeline

# Linearizability plane (analysis/linearize.py; README
# "Linearizability"): the checker's own violation corpus
# (tests/linearize_corpus — every known-bad history flagged with a
# counterexample window, every known-good one clean), the interval-
# model units, and the concurrent tier's bounded slices: N clients
# writing overlapping keys through member churn, every history
# checked per key (invariant 9).  The full 120-schedule campaign
# runs under the slow marker (pytest tests/test_linearize.py -m
# slow).  Rerun a failing seed with `python -m zkstream_tpu chaos
# --tier ensemble --clients 3 --seed N --schedules 1`.
linearize:
	$(PYTHON) -m pytest tests/test_linearize.py -q -m 'not slow'
	$(PYTHON) -m pytest tests/test_chaos_ensemble.py -q \
	    -k 'concurrent' -m 'not slow'

check: analyze cache
	$(PYTHON) tools/lint.py $(LINT_TARGETS)

# Semantic static analysis (tools/zkanalyze.py -> zkstream_tpu/
# analysis/): the contract tier above lint — loop-blocking,
# await-under-lock, span-leak, fault-order and knob/metric drift,
# one checker per rule the PR trail established (README "Static
# analysis").  Zero findings on the package is the committed
# baseline; suppressions demand a reason and are listed with
# `python tools/zkanalyze.py --list-suppressions`.
analyze:
	$(PYTHON) tools/zkanalyze.py zkstream_tpu

# Build the native host codecs (zkwire.cpp C-ABI scanner and the
# zkwire_ext.c CPython-extension decoder).  Optional: the runtime
# degrades to pure Python without them.
native:
	$(PYTHON) -c "from zkstream_tpu.utils import native; \
	    p = native.build(); print(p or 'native build unavailable'); \
	    q = native.build_ext(); print(q or 'ext build unavailable'); \
	    r = native.build_loadgen(); \
	    print(r or 'loadgen build unavailable')"

# Build the raw-socket C load generator (tools/loadgen.c ->
# native/zkloadgen.vN).  Same capability-probed discipline as the
# codecs: graceful skip without a compiler.
loadgen:
	$(PYTHON) -c "from zkstream_tpu.utils import native; \
	    p = native.build_loadgen(); \
	    print(p or 'loadgen build unavailable')"

# Memory-safety check: AddressSanitizer build of the extension driven
# with valid corpora + a 20k-round mutation storm (tools/asan_check.py).
asan:
	$(PYTHON) tools/asan_check.py

# Undefined-behavior check: the same corpora + storm through a
# -fsanitize=undefined -fno-sanitize-recover build, so shift/overflow/
# alignment UB aborts instead of silently miscomputing.
ubsan:
	$(PYTHON) tools/asan_check.py --ubsan

# Both sanitizer drives, back to back.
sanitize: asan ubsan

# Line coverage (reference Makefile:61-66 istanbul analogue).  No
# coverage package in this image; tools/cover.py implements it on
# sys.monitoring (PEP 669) — once-per-line callbacks with DISABLE, so
# the suite runs at near-native speed.  Writes COVERAGE.txt.
coverage: native
	$(PYTHON) tools/cover.py tests/ -q

clean:
	rm -f COVERAGE.txt
	rm -rf native/*.so native/*.so.tmp.* native/zkloadgen.v* \
	    $$(find . -name __pycache__ -not -path './.git/*') \
	    .pytest_cache .jax_cache
