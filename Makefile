# Developer entry points. The same commands the CI tiers run — no
# extra tooling, everything here works with the stdlib + the baked-in
# JAX toolchain.

PYTHON ?= python

.PHONY: lint test replay autoscale-soak noisy-neighbor router-soak \
	benchgate simulate chaos-sim slo-report model-fleet-soak

# omelint: the repo's static-analysis gate (docs/static-analysis.md).
# Runs every registered analyzer over ome_tpu/ and fails on any
# finding that is neither inline-suppressed (with a reason) nor
# grandfathered in lint-baseline.json.
lint:
	$(PYTHON) scripts/omelint.py --all

# tier-1: the fast correctness suite (see ROADMAP.md for the exact
# CI invocation with log capture)
test:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

# bench regression gate (docs/perf-attribution.md): run bench.py
# fresh and diff it against the newest match of BENCH_HISTORY (a glob
# of BENCH_r*.json results; the repo keeps none of its own) with
# noise-aware per-metric bands; non-zero exit on regression. Known,
# accepted regressions go in bench-waivers.json with a reason.
benchgate:
	$(PYTHON) scripts/perfgate.py --run --history '$(BENCH_HISTORY)'

# fleet simulator smoke (docs/simulation.md): the autoscale scenario
# (diurnal + flash-crowd trace through the real controller on virtual
# time) run twice with the same seed; fails unless the two reports —
# decision log included — are byte-identical
simulate:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/simulate.py \
		--scenario autoscale --seed 7 --check-determinism --full

# fleet-scale chaos in the simulator (docs/simulation.md): a seeded
# fault schedule — kill/restart, slow/stuck replicas, partitions,
# transport faults — against 100 engines with the fleet-wide
# durability invariants checked (no admitted request lost, every
# journal reconciled), run twice for byte-identity. Exit 2 =
# invariant violation; add --shrink --bundle-dir to minimize it.
chaos-sim:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/simulate.py \
		--scenario chaos --seed 7 --engines 100 --requests 2000 \
		--kills 12 --check-determinism

# fleet SLO report (docs/slo.md): the steady scenario through the
# virtual-time SLO engine, printing the per-class attainment /
# error-budget / alert-state table to stderr (canonical JSON report
# on stdout, pipe it somewhere if you want it)
slo-report:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/simulate.py \
		--scenario steady --seed 7 --slo-table >/dev/null

# trace replay against a self-spawned router + CPU engine: the quick
# "does the load generator work here" check (docs/autoscaling.md);
# point scripts/replay.py at --url/--trace for real endpoints/logs
replay:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/replay.py --topology 1 \
		--seed 7 --requests 10 --compress 2

# multi-tenant isolation under overload (docs/multi-tenancy.md): a
# seeded batch-class flood at 5x slot capacity with steady
# interactive traffic and a mid-episode SIGKILL, checked against the
# noisy-neighbor invariants (no admitted class starves, weighted
# shares hold, interactive is never shed)
noisy-neighbor:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/chaos_soak.py --seed 7 \
		--episodes 1 --noisy-neighbor --prefill 0 --decode 0 \
		--unified 1 --spread 4

# ingress HA under router loss (docs/router-ha.md): three gossiping
# async routers front two engines, one takes a keyed forward fault
# and is SIGKILLed mid-replay; the driver fails over client-side and
# the runner checks the HA invariants (no request lost or duplicated
# fleet-wide, survivors converge on the victim's breaker
# observations within one anti-entropy round)
router-soak:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/chaos_soak.py --seed 3 \
		--episodes 1 --router-loss --routers 3 --prefill 0 \
		--decode 0 --unified 2 --requests 10 --spread 4

# hardened weight plane under mid-download SIGKILLs
# (docs/model-fleet.md): seeded episodes that kill the model agent
# after a seed-derived number of objects are manifest-recorded, then
# check the failure contract — serving path never partial, manifest
# never ahead of the disk, re-run resumes from every verified object
# and publishes a byte-identical tree
model-fleet-soak:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/modelfleet_soak.py --seed 7 \
		--episodes 5

# the closed-loop demo: bursty replayed trace + SLO-aware scaling of
# a live engine pool, reporting engine-seconds vs static max
# provisioning and the full decision log
autoscale-soak:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/autoscale.py --seed 7 \
		--requests 30 --burst-factor 6 --min-engines 1 \
		--max-engines 3 --slo-ttft-p99 0.5 --slo-queue-wait-p99 \
		0.25 --queue-depth-high 2 --settle-seconds 10 --json
