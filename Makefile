# Developer entry points. `make check` is the local quality gate mirrored by
# .github/workflows/ci.yml.

.PHONY: check test lint native bench bench-prepare bench-dataset bench-io bench-io-remote bench-io-write remote-write-smoke bench-write bench-encode encode-smoke bench-assembly bench-serve bench-query bench-device device-smoke bench-chaos chaos-smoke bench-compare bench-record bench-trend obs-smoke fleet-smoke mesh-smoke ingest-smoke bench-ingest bench-serve-mesh profile-live dryrun fuzz profile

# tier-1 excludes `slow` (extended fault sweeps); `make fuzz` includes them;
# chaos-smoke runs the scripted fault schedule end to end at smoke scale;
# obs-smoke validates the bench trend store's schema and pins the
# sampling profiler's overhead on a decode loop; encode-smoke pins the
# fused native encoder byte-identical to the staged Python rung;
# device-smoke pins the device query/write paths byte-identical to the
# host engines (fast subset of tests/test_device_query.py);
# remote-write-smoke pins the multipart sink's zero-torn-object contract
# over real loopback HTTP (fast subset of tests/test_remote_sink.py);
# fleet-smoke pins the mesh telemetry plane (fast subset of
# tests/test_mesh.py): two in-process daemons -> federated /metrics
# scrape (counters summed exactly) -> cross-process trace-merge round trip;
# mesh-smoke pins the sharded-serve router (fast subset of
# tests/test_mesh_router.py): routed scan/query byte-identical to one
# daemon + a replica killed mid-hammer costing typed retries only;
# ingest-smoke pins the data-lake write loop (fast subset of
# tests/test_lake.py): the append/scan/compact concurrency hammer,
# crash-mid-compact zero-loss, and time-travel byte-identity
check: native lint chaos-smoke obs-smoke encode-smoke device-smoke remote-write-smoke fleet-smoke mesh-smoke ingest-smoke
	python -m pytest tests/ -q -m 'not slow'

# ruff (config in ruff.toml) when installed; images without it fall back to
# the compileall syntax gate so `make check` stays runnable everywhere
lint:
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check parquet_tpu/ tests/ bench.py; \
	else \
		echo "lint: ruff not installed; running compileall syntax gate instead"; \
		python -m compileall -q parquet_tpu tests bench.py __graft_entry__.py; \
	fi

test:
	python -m pytest tests/ -q -m 'not slow'

native:
	$(MAKE) -C native

bench:
	python bench.py

# host prepare microbench: serial wall + per-stage breakdown (decompress /
# levels / prescan / copy) + GIL-free thread scaling; no accelerator needed
bench-prepare: native
	python bench.py --phase prepare

# streaming-loader bench: multi-file glob through ParquetDataset at a
# prefetch-depth sweep (rows/s + wait-time share); host-only, no accelerator
bench-dataset: native
	python bench.py --dataset

# io-layer bench: coalesce-gap + readahead-depth sweeps against a
# latency-injected FlakySource (the object-store shape); host-only
bench-io: native
	python bench.py --io

# remote-IO bench: httpstub (real loopback HTTP range GETs) at injected
# RTT 0/5/25 ms — auto-tuned coalesce/readahead vs the fixed local knobs,
# plus the tiered RAM->disk cache's warm re-scan (asserted to read ZERO
# source bytes before timing); host-only
bench-io-remote: native
	python bench.py --io-remote

# remote-WRITE bench: HttpSink's multipart protocol into a writable
# httpstub at injected RTT 0/5/25 ms, part-size sweep 2/4/8 MiB, every
# committed object asserted byte-identical before timing; host-only
bench-io-write: native
	python bench.py --io-write

# the make-check-sized remote-write gate: a signed FileWriter(url) ->
# FileReader(url) round trip plus the atomicity pins (no object visible
# before complete, none after abort) over real loopback HTTP
remote-write-smoke: native
	JAX_PLATFORMS=cpu python -m pytest tests/test_remote_sink.py -q -k 'roundtrip or torn or signed or abort'

# write-path bench: FileWriter vs pyarrow + the pqt-encode parallelism
# sweep (pool 1/4/8 x 8/16 row groups, byte-identical to serial); host-only
bench-write: native
	python bench.py --write

# fused-vs-staged encode ladder: per-shape serial chunk-encode throughput
# (dict-string/dict-int/delta/plain), byte-identity asserted pre-timing;
# skips cleanly when the native extension is not built
bench-encode: native
	python bench.py --encode

# the make-check-sized encode gate: the fused native encoder must produce
# bytes IDENTICAL to the staged Python rung across the small
# encodings x codecs x dpv matrix (skips cleanly without the extension)
encode-smoke: native
	JAX_PLATFORMS=cpu python -m pytest tests/test_sink.py -q -k 'FusedEncodeLadder and (matrix or crc or page)'

# scan-service bench: requests/s + p50/p99 latency at client concurrency
# 1/4/16 against a warm in-process daemon over real HTTP, plus the
# cold-vs-warm /v1/plan latency ratio; host-only, no accelerator
bench-serve: native
	python bench.py --serve

# query push-down bench: vectorized vs scalar residual filtering on a
# 1M-row numeric predicate, and filtered-AGGREGATE req/s (POST /v1/query)
# vs row-streaming req/s of the same predicate; host-only, no accelerator
bench-query: native
	python bench.py --query

# HBM-loop bench: device-vs-host filter / aggregate / write timings (byte
# identity asserted before any timer starts); refuses to run without a TPU
# unless JAX_PLATFORMS=cpu asks for the CPU by name
bench-device: native
	python bench.py --device

device-smoke: native
	JAX_PLATFORMS=cpu python -m pytest tests/test_device_query.py -q -k 'engages or fast or requires or host_config'

# chaos bench: the scripted fault schedule (latency spike -> error burst ->
# blackout -> recovery) against the SLO-controlled dataset pipeline vs
# uncontrolled, breaker fast-fail vs the retry ladder, and the serve daemon
# under brownout; "SLO held through the schedule" as a measured artifact
bench-chaos: native
	python bench.py --chaos

# the make-check-sized chaos gate: same code paths, sub-second phases
chaos-smoke: native
	PQT_CHAOS_SMOKE=1 JAX_PLATFORMS=cpu python bench.py --chaos

# record-assembly bench: vectorized level-scan engine vs scalar cursor walk
# vs pyarrow to_pylist on flat/1-level/2-level tables (rows asserted
# identical before timing); host-only, no accelerator
bench-assembly: native
	python bench.py --assembly

# regression gate over two --json artifacts: every tracked metric's
# new/old ratio, non-zero exit on a >THRESHOLD regression — how future
# PRs hold the BENCH_r0x trajectory. Usage:
#   make bench-compare OLD=BENCH_r06.json NEW=/tmp/bench_now.json
# (omit OLD to diff against the latest round in BENCH_history.jsonl)
bench-compare:
	python bench.py --compare $(OLD) $(NEW) --threshold $(or $(THRESHOLD),0.10)

# capture a full bench round AND append it to the persistent trend store
# (BENCH_history.jsonl: artifact + git rev + config fingerprint). LABEL
# names the round (default rNN); the trend renders with `make bench-trend`
bench-record: native
	python bench.py --json /tmp/pqt_bench_now.json
	python bench.py --record /tmp/pqt_bench_now.json $(if $(LABEL),--label $(LABEL))

# every tracked metric across the recorded rounds, last-vs-first ratio
bench-trend:
	python bench.py --trend $(if $(SECTION),--section $(SECTION))

# the make-check-sized observability gate: the trend store's schema must
# parse (a malformed BENCH_history.jsonl exits non-zero) and the sampling
# profiler's measured overhead on a decode loop must hold its <5% pin
obs-smoke: native
	python bench.py --trend > /dev/null
	JAX_PLATFORMS=cpu python -m pytest tests/test_prof.py -q -k overhead

# the make-check-sized mesh-telemetry gate: two in-process daemons, a
# federated /metrics scrape whose merged counters equal the arithmetic
# sum of the replica scrapes, and a client trace-id ridden through two
# daemons' remote GETs then stitched by `parquet-tool trace-merge`
fleet-smoke: native
	JAX_PLATFORMS=cpu python -m pytest tests/test_mesh.py -q -k 'fleet_smoke or round_trip or Exactness'

# sharded-serve smoke: replicas + router in-process, routed results
# byte-identical to a single daemon, one replica killed mid-hammer
mesh-smoke: native
	JAX_PLATFORMS=cpu python -m pytest tests/test_mesh_router.py -q -k 'mesh_smoke or byte_identical or killed'

# the make-check-sized data-lake gate: concurrent append/scan/compact
# with every scan pinning exactly one generation, a crash-mid-compact
# losing nothing, and open_snapshot(gen=k) byte-identical across later
# compactions
ingest-smoke: native
	JAX_PLATFORMS=cpu python -m pytest tests/test_lake.py -q -k 'hammer or exactly_one or crash or time_travel or byte_identical'

# data-lake loop benchmark (writes the "ingest" artifact section):
# sustained append rows/s + the compaction payoff (pruned-ratio gain,
# filtered-scan speedup)
bench-ingest: native
	python bench.py --ingest

# router scaling + chaos benchmark (writes the "mesh" artifact section)
bench-serve-mesh:
	python bench.py --serve-mesh


# live-profile a RUNNING daemon (flamegraph-compatible collapsed stacks,
# lane-attributed to the pqt-* pools): make profile-live URL=host:port
profile-live:
	python -m parquet_tpu.tools.parquet_tool profile --live $(or $(URL),http://127.0.0.1:8080) --seconds $(or $(SECONDS),2)

dryrun:
	python -c "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8)"

# seeded fault-injection matrix, fast subset AND the extended `slow` sweep —
# fully deterministic (numpy default_rng from fixed seeds), so a failure here
# replays exactly; the fast subset also rides the tier-1 `-m 'not slow'` run
fuzz: native
	python -m pytest tests/test_faults.py -q

# observability smoke: generate a file, decode it under the span tracer via
# `parquet-tool profile` (jax forced onto the CPU: host-side spans need no
# device), then validate the Chrome trace-event JSON parses
profile:
	python -c "import numpy as np; from parquet_tpu.core.writer import FileWriter; from parquet_tpu.schema.dsl import parse_schema; s = parse_schema('message m { required int64 id; required binary name (UTF8); }'); w = FileWriter('/tmp/pqt_profile.parquet', s, codec='snappy'); w.write_column('id', np.arange(200000, dtype=np.int64)); w.write_column('name', ['n%d' % (i % 97) for i in range(200000)]); w.close()"
	python -m parquet_tpu.tools.parquet_tool profile /tmp/pqt_profile.parquet -o /tmp/pqt_profile_trace.json --metrics --cpu
	python -c "import json; d = json.load(open('/tmp/pqt_profile_trace.json')); assert d['traceEvents'], 'empty trace'; print('profile: %d trace events parse OK' % len(d['traceEvents']))"

