"""The performance observatory's contracts: per-tenant cost accounting,
the live profiler and config endpoints on the serve daemon, the
metric→trace exemplar link, and the persistent bench trend store.

Pinned here:
  * CostLedger bounds and arithmetic (overflow bucket, unit_clock CPU
    attribution through the contextvar, trace-rollup byte charges);
  * a 3-tenant concurrent hammer whose per-tenant CPU/byte totals
    reconcile with process-level counters, with label cardinality held
    under adversarial X-Tenant values;
  * GET /v1/debug/tenants, /v1/debug/vars, /v1/debug/profile (collapsed/
    top/json + typed 400/409s) on a live daemon;
  * the OpenMetrics exemplar on serve_request_seconds carrying a
    request-id that resolves in the flight recorder — dashboard spike →
    exact trace, the full loop;
  * `bench.py --record` / `--trend` / one-arg `--compare` round-tripping
    artifacts through BENCH_history.jsonl, including the schema check
    `make check` leans on;
  * `parquet-tool debug --vars/--tenants` and `profile --live`.
"""

from __future__ import annotations

import http.client
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from parquet_tpu.obs.cost import (
    CostLedger,
    charge_request_from_trace,
    cost_context,
    unit_clock,
)
from parquet_tpu.serve import ScanServer, ServeConfig
from parquet_tpu.tools.parquet_tool import main as tool_main
from parquet_tpu.utils import metrics
from parquet_tpu.utils.trace import add_bytes, bump, decode_trace, stage

WATCHDOG_S = 30.0
BENCH = str(Path(__file__).resolve().parent.parent / "bench.py")

ROWS = 3000
ROW_GROUP = 1000


# -- fixtures ------------------------------------------------------------------


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    d = tmp_path_factory.mktemp("obsy_corpus")
    rng = np.random.default_rng(5)
    t = pa.table(
        {
            "id": pa.array(np.arange(ROWS, dtype=np.int64)),
            "v": pa.array(rng.standard_normal(ROWS).astype(np.float64)),
            "name": pa.array([f"n{i % 13}" for i in range(ROWS)]),
        }
    )
    pq.write_table(t, str(d / "a.parquet"), row_group_size=ROW_GROUP)
    return d


@pytest.fixture()
def server(corpus):
    with ScanServer(ServeConfig(port=0, root=str(corpus), cache_mb=16)) as s:
        s.start_background()
        s.service.ledger.reset()  # per-test ledger isolation
        yield s


def _request(server, method, path, body=None, headers=None, timeout=WATCHDOG_S):
    conn = http.client.HTTPConnection(server.host, server.port, timeout=timeout)
    try:
        conn.request(
            method,
            path,
            body=json.dumps(body).encode() if body is not None else None,
            headers=headers or {},
        )
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def _scan(server, tenant, request_id=None):
    headers = {"X-Tenant": tenant}
    if request_id is not None:
        headers["X-Request-Id"] = request_id
    status, hdrs, body = _request(
        server, "POST", "/v1/scan", {"paths": ["*.parquet"]}, headers
    )
    assert status == 200, body[:200]
    return hdrs, body


# -- the cost ledger -----------------------------------------------------------


class TestCostLedger:
    def test_charges_accumulate_and_table_sorts_by_cpu(self):
        led = CostLedger()
        led.charge_cpu("b", 0.2)
        led.charge_cpu("a", 0.5)
        led.charge_request("a", decoded_bytes=100, payload_bytes=10)
        rows = led.table()
        assert [r["tenant"] for r in rows] == ["a", "b"]
        assert rows[0]["cpu_seconds"] == pytest.approx(0.5)
        assert rows[0]["decoded_bytes"] == 100 and rows[0]["requests"] == 1
        totals = led.totals()
        assert totals["cpu_seconds"] == pytest.approx(0.7)
        assert totals["units"] == 2

    def test_bounded_tenants_collapse_to_overflow(self):
        led = CostLedger(max_tenants=2)
        for name in ("t1", "t2", "hostile3", "hostile4", "hostile5"):
            led.charge_cpu(name, 0.01)
        rows = led.table()
        names = {r["tenant"] for r in rows}
        assert names == {"t1", "t2", "__overflow__"}
        # nothing was dropped: totals stay exact
        assert led.totals()["cpu_seconds"] == pytest.approx(0.05)

    def test_hostile_keys_truncate(self):
        led = CostLedger()
        led.charge_cpu("x" * 500, 0.01)
        [row] = led.table()
        assert len(row["tenant"]) == 64

    def test_metric_families_ride_charges(self):
        reg = metrics.MetricsRegistry()
        led = CostLedger(registry=reg)
        led.charge_cpu("alice", 0.25)
        led.charge_request("alice", decoded_bytes=1234)
        assert reg.get(
            "serve_tenant_cpu_seconds_total", tenant="alice"
        ) == pytest.approx(0.25)
        assert reg.get(
            "serve_tenant_decoded_bytes_total", tenant="alice"
        ) == 1234

    def test_unit_clock_charges_context_tenant_cpu(self):
        led = CostLedger(registry=metrics.MetricsRegistry())
        with cost_context("carol"):
            with unit_clock(ledger=led):
                # real CPU, not sleep: thread_time only counts cycles
                x = 0
                for i in range(400_000):
                    x += i
        [row] = led.table()
        assert row["tenant"] == "carol"
        assert row["cpu_seconds"] > 0 and row["units"] == 1

    def test_unit_clock_outside_context_charges_nothing(self):
        led = CostLedger(registry=metrics.MetricsRegistry())
        with unit_clock(ledger=led):
            pass
        assert led.table() == []

    def test_charge_request_from_trace_reads_rollup(self):
        led = CostLedger(registry=metrics.MetricsRegistry())
        with decode_trace() as t:
            with stage("decode"):
                add_bytes("decode.bytes", 5000)
            with stage("io.read", nbytes=0):
                add_bytes("io.read", 800)
            bump("io_cache_hit")
            bump("io_cache_hit")
            bump("io_cache_miss")
        charge_request_from_trace("dave", t, nbytes=42, ledger=led)
        [row] = led.table()
        assert row["decoded_bytes"] == 5000
        assert row["source_bytes"] == 800
        assert row["payload_bytes"] == 42
        assert row["cache_hits"] == 2 and row["cache_misses"] == 1
        assert row["requests"] == 1

    def test_validation(self):
        with pytest.raises(ValueError):
            CostLedger(max_tenants=0)


# -- the daemon's cost/debug endpoints -----------------------------------------


class TestTenantAccounting:
    def test_three_tenant_hammer_reconciles(self, server):
        """The acceptance pin: under a 3-tenant concurrent hammer the
        per-tenant CPU/byte attributions sum to the process totals
        within tolerance, and equal work bills equally."""
        snap0 = metrics.snapshot()
        # the registry is the process's: a suite that ran in this worker
        # before may have billed a tenant of the same name
        billed0 = {
            name: metrics.get("serve_tenant_decoded_bytes_total", tenant=name)
            for name in ("alice", "bob", "carol")
        }
        cpu0 = time.process_time()
        per_tenant = 3
        errors = []

        def hammer(tenant):
            try:
                for _ in range(per_tenant):
                    _scan(server, tenant)
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append(e)

        threads = [
            threading.Thread(target=hammer, args=(t,))
            for t in ("alice", "bob", "carol")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WATCHDOG_S)
        assert not errors, errors
        cpu_delta = time.process_time() - cpu0
        mdelta = metrics.delta(snap0)

        # a handler bills its tenant AFTER the last byte went out, so the
        # last scans' charges may still be landing when the clients return:
        # read the ledger until it has settled (what is asserted is the
        # settled ledger, not the race)
        deadline = time.monotonic() + 5.0
        while True:
            status, _, body = _request(server, "GET", "/v1/debug/tenants")
            assert status == 200
            doc = json.loads(body)
            rows = {r["tenant"]: r for r in doc["tenants"]}
            settled = all(
                rows.get(n, {}).get("requests") == per_tenant
                for n in ("alice", "bob", "carol")
            )
            if settled or time.monotonic() > deadline:
                break
            time.sleep(0.02)
        mdelta = metrics.delta(snap0)
        assert set(rows) >= {"alice", "bob", "carol"}
        for name in ("alice", "bob", "carol"):
            r = rows[name]
            assert r["requests"] == per_tenant
            assert r["cpu_seconds"] > 0
            assert r["decoded_bytes"] > 0
            assert r["payload_bytes"] > 0
            assert r["units"] == per_tenant * (ROWS // ROW_GROUP)
        # equal work bills equal bytes, exactly
        assert (
            rows["alice"]["decoded_bytes"]
            == rows["bob"]["decoded_bytes"]
            == rows["carol"]["decoded_bytes"]
        )
        totals = doc["totals"]
        # CPU: the tenants' sum can never exceed what the process spent,
        # and executor units must be a meaningful share of it
        assert totals["cpu_seconds"] <= cpu_delta + 0.25
        assert totals["cpu_seconds"] > 0
        # decoded bytes reconcile with the process counter: the ledger is
        # fed from the SAME choke point (decompress_block mirrors its
        # output bytes into each request's trace), so the tenant sum
        # equals the bytes_uncompressed_total delta
        uncompressed = sum(
            v
            for k, v in mdelta.items()
            if k.startswith("bytes_uncompressed_total")
        )
        assert uncompressed > 0
        assert totals["decoded_bytes"] == pytest.approx(uncompressed, rel=0.02)
        # and the always-on families carry the same story
        for name in ("alice", "bob", "carol"):
            assert (
                metrics.get("serve_tenant_cpu_seconds_total", tenant=name) > 0
            )
            assert (
                metrics.get("serve_tenant_decoded_bytes_total", tenant=name)
                - billed0[name]
                == rows[name]["decoded_bytes"]
            )

    def test_adversarial_tenant_values_stay_bounded(self, server):
        """Hostile X-Tenant headers: truncated to the admission key form,
        label-escaped in the exposition, and the daemon stays typed."""
        # (a raw \n in a header value is refused by http.client itself —
        # it cannot even reach the daemon; a tab is legal in Prometheus
        # label values but another suite regex-pins whitespace-free
        # samples on the process registry, so stress braces instead)
        hostile = ["x" * 500, 'evil"quote', 'evil{inj="1"}', "  "]
        for h in hostile:
            _scan(server, h)
        status, _, body = _request(server, "GET", "/v1/debug/tenants")
        doc = json.loads(body)
        for r in doc["tenants"]:
            assert len(r["tenant"]) <= 64
        # the whitespace-only header collapsed to the default key
        assert "default" in {r["tenant"] for r in doc["tenants"]}
        status, _, text = _request(server, "GET", "/metrics")
        assert status == 200
        exposition = text.decode()
        for line in exposition.splitlines():
            assert "\n" not in line  # trivially true: the split is the pin
        # the quote arrived escaped, never raw
        assert 'evil\\"quote' in exposition

    def test_debug_vars_snapshot(self, server):
        status, _, body = _request(server, "GET", "/v1/debug/vars")
        assert status == 200
        doc = json.loads(body)
        assert doc["pid"] == os.getpid()
        assert doc["uptime_s"] >= 0
        assert doc["version"]
        assert doc["serve"]["max_inflight"] == 32
        assert doc["serve"]["cache_mb"] == 16
        assert doc["obs"]["debug_ring_size"] > 0
        assert set(doc["resilience"]) == {"breaker", "retry", "hedge"}
        assert "depths" in doc["pools"]
        # the uptime gauge rides the registry for scrapers too
        status, _, text = _request(server, "GET", "/metrics")
        assert "parquet_tpu_process_uptime_seconds" in text.decode()


class TestLiveProfile:
    def test_profile_attributes_serve_lanes_under_load(self, server):
        """The acceptance pin: a live profile window on a serving daemon
        returns a non-empty collapsed profile attributing samples to the
        named pqt-* lanes."""
        stop = threading.Event()
        errors = []

        def hammer():
            while not stop.is_set():
                try:
                    _scan(server, "prof")
                except Exception as e:  # pragma: no cover
                    if not stop.is_set():
                        errors.append(e)
                    return

        th = threading.Thread(target=hammer)
        th.start()
        try:
            status, hdrs, body = _request(
                server,
                "GET",
                "/v1/debug/profile?seconds=0.8&interval_ms=5",
                timeout=WATCHDOG_S,
            )
        finally:
            stop.set()
            th.join(WATCHDOG_S)
        assert not errors, errors
        assert status == 200
        text = body.decode()
        assert text.strip(), "empty collapsed profile"
        lanes = {line.split(";", 1)[0] for line in text.splitlines()}
        assert any(lane.startswith("pqt-") for lane in lanes), lanes
        # every line is collapsed-stack shaped: frames then a count
        for line in text.splitlines():
            stack, _, count = line.rpartition(" ")
            assert count.isdigit() and ";" in stack

    def test_profile_top_and_json_formats(self, server):
        status, _, body = _request(
            server, "GET", "/v1/debug/profile?seconds=0.2&format=top"
        )
        assert status == 200
        assert body.decode().startswith("profile:")
        status, _, body = _request(
            server, "GET", "/v1/debug/profile?seconds=0.2&format=json"
        )
        assert status == 200
        doc = json.loads(body)
        assert {"samples", "lanes", "stacks", "interval_s"} <= set(doc)

    @pytest.mark.parametrize(
        "qs",
        [
            "seconds=0",
            "seconds=61",
            "seconds=nope",
            "seconds=1&interval_ms=0.1",
            "seconds=1&format=svg",
        ],
    )
    def test_bad_params_are_typed_400s(self, server, qs):
        status, _, body = _request(
            server, "GET", f"/v1/debug/profile?{qs}"
        )
        assert status == 400
        assert json.loads(body)["error"]["code"] == "bad_request"

    def test_concurrent_window_is_typed_409(self, server):
        results = {}

        def long_window():
            results["first"] = _request(
                server, "GET", "/v1/debug/profile?seconds=1.5"
            )

        th = threading.Thread(target=long_window)
        th.start()
        time.sleep(0.3)  # let the first window take the capture lock
        status, _, body = _request(
            server, "GET", "/v1/debug/profile?seconds=0.2"
        )
        th.join(WATCHDOG_S)
        assert results["first"][0] == 200
        assert status == 409
        assert json.loads(body)["error"]["code"] == "profile_in_progress"


class TestExemplarLoop:
    def test_latency_bucket_names_a_fetchable_request(self, server):
        """The metric→trace link end to end: scan with a known id, then
        the OpenMetrics exposition's serve_request_seconds bucket carries
        that id as an exemplar, and the id resolves in the flight
        recorder."""
        rid = "exemplar-loop-1"
        _scan(server, "alice", request_id=rid)
        status, hdrs, body = _request(
            server,
            "GET",
            "/metrics",
            headers={"Accept": "application/openmetrics-text"},
        )
        assert status == 200
        assert "application/openmetrics-text" in hdrs["Content-Type"]
        om = body.decode()
        assert om.rstrip().endswith("# EOF")
        ex_lines = [
            ln
            for ln in om.splitlines()
            if "serve_request_seconds_bucket" in ln and " # {" in ln
        ]
        assert ex_lines, "no exemplar on serve_request_seconds"
        ids = {
            ln.split('request_id="', 1)[1].split('"', 1)[0]
            for ln in ex_lines
            if 'request_id="' in ln
        }
        assert rid in ids
        # the loop closes: the id the dashboard shows fetches the record
        status, _, body = _request(
            server, "GET", f"/v1/debug/requests/{rid}"
        )
        assert status == 200
        rec = json.loads(body)
        assert rec["id"] == rid and rec["status"] == 200
        # ... and the record's stage rollup is exclusive: inner decode
        # stages under serve.execute carry their nested share
        stages = rec["stages"]
        assert "serve.execute" in stages
        assert "nested_seconds" not in stages["serve.execute"]
        assert any(
            "nested_seconds" in s
            for name, s in stages.items()
            if name != "serve.execute"
        )

    def test_classic_scrape_unchanged(self, server):
        _scan(server, "alice")
        status, hdrs, body = _request(server, "GET", "/metrics")
        assert status == 200
        assert hdrs["Content-Type"].startswith("text/plain")
        text = body.decode()
        assert "# EOF" not in text and " # {" not in text


# -- the CLI surfaces ----------------------------------------------------------


class TestDebugCLI:
    def test_debug_vars_and_tenants(self, server, capsys):
        _scan(server, "alice")
        assert tool_main(["debug", server.url, "--vars"]) == 0
        out = capsys.readouterr().out
        doc = json.loads(out)
        assert doc["pid"] == os.getpid()
        assert tool_main(["debug", server.url, "--tenants"]) == 0
        out = capsys.readouterr().out
        assert "TENANT" in out and "alice" in out and "TOTAL" in out

    def test_profile_live(self, server, capsys, tmp_path):
        assert (
            tool_main(
                ["profile", "--live", server.url, "--seconds", "0.2"]
            )
            == 0
        )
        out = capsys.readouterr().out
        assert out.strip()
        assert all(" " in ln for ln in out.strip().splitlines())
        outfile = tmp_path / "collapsed.txt"
        assert (
            tool_main(
                [
                    "profile",
                    "--live",
                    server.url,
                    "--seconds",
                    "0.2",
                    "--top",
                    "-o",
                    str(outfile),
                ]
            )
            == 0
        )
        assert outfile.read_text().startswith("profile:")

    def test_profile_file_mode_still_requires_args(self, capsys):
        assert tool_main(["profile"]) == 2

    def test_profile_cross_mode_flags_are_refused(self, server, capsys):
        # live-only flags in file mode: refused, not silently dropped
        assert tool_main(["profile", "f.parquet", "-o", "t.json", "--top"]) == 2
        assert "--live mode only" in capsys.readouterr().err
        # file-mode flags against a daemon: refused too
        rc = tool_main(
            ["profile", "--live", server.url, "--columns", "a,b"]
        )
        assert rc == 2
        assert "file mode" in capsys.readouterr().err

    def test_profile_live_unreachable_is_typed(self, capsys):
        rc = tool_main(
            ["profile", "--live", "http://127.0.0.1:9", "--seconds", "0.1"]
        )
        assert rc == 1
        assert "cannot reach" in capsys.readouterr().err


# -- the bench trend store -----------------------------------------------------


def _bench(*args, cwd):
    return subprocess.run(
        [sys.executable, BENCH, *args],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        cwd=str(cwd),
        timeout=120,
    )


class TestBenchTrendStore:
    def _artifact(self, tmp_path, name, value, rps):
        art = {
            "value": value,
            "unit": "rows/s",
            "serve": {"concurrency_sweep": {"16": {"rps": rps, "p99_ms": 100}}},
        }
        p = tmp_path / name
        p.write_text(json.dumps(art))
        return p

    def test_record_trend_compare_round_trip(self, tmp_path):
        hist = tmp_path / "hist.jsonl"
        a1 = self._artifact(tmp_path, "a1.json", 100.0, 5.0)
        a2 = self._artifact(tmp_path, "a2.json", 104.0, 5.2)
        r = _bench(
            "--record", str(a1), "--label", "r06", "--history", str(hist),
            cwd=tmp_path,
        )
        assert r.returncode == 0, r.stdout
        out = r.stdout.decode()
        assert "r06" in out and "tracked" in out
        # provenance rides every entry
        [entry] = [
            json.loads(ln) for ln in hist.read_text().splitlines() if ln
        ]
        assert entry["label"] == "r06"
        assert entry["git_rev"] and entry["config"]
        assert entry["artifact"]["value"] == 100.0
        r = _bench("--record", str(a2), "--history", str(hist), cwd=tmp_path)
        assert r.returncode == 0
        # the label-less record CONTINUES the rNN sequence past the
        # seeded round instead of restarting at r02 and colliding later
        labels = [
            json.loads(ln)["label"]
            for ln in hist.read_text().splitlines()
            if ln
        ]
        assert labels == ["r06", "r07"]
        # trend renders both rounds with the last-vs-first ratio
        r = _bench("--trend", "--history", str(hist), cwd=tmp_path)
        assert r.returncode == 0, r.stdout
        out = r.stdout.decode()
        assert "2 rounds" in out
        assert "value" in out and "x1.040" in out
        # one-arg compare defaults to the LATEST recorded round
        r = _bench("--compare", str(a2), "--history", str(hist), cwd=tmp_path)
        assert r.returncode == 0, r.stdout
        assert "no tracked regressions" in r.stdout.decode()
        # a regressing artifact fails the same one-arg gate
        bad = self._artifact(tmp_path, "bad.json", 80.0, 4.0)
        r = _bench("--compare", str(bad), "--history", str(hist), cwd=tmp_path)
        assert r.returncode == 1
        assert "REGRESSION" in r.stdout.decode()

    def test_record_prefers_run_time_fingerprint(self, tmp_path):
        """An artifact stamped with bench_config at --json time records
        THAT fingerprint, not the env of the --record shell."""
        hist = tmp_path / "hist.jsonl"
        art = {
            "value": 1.0,
            "bench_config": {"fingerprint": "cafe0123beef", "basis": {}},
        }
        p = tmp_path / "a.json"
        p.write_text(json.dumps(art))
        assert (
            _bench(
                "--record", str(p), "--history", str(hist), cwd=tmp_path
            ).returncode
            == 0
        )
        [entry] = [json.loads(ln) for ln in hist.read_text().splitlines() if ln]
        assert entry["config"] == "cafe0123beef"

    def test_json_artifact_carries_run_config(self, tmp_path):
        """Artifacts written via --json embed the run-time config
        fingerprint (what --record prefers over its own shell's env)."""
        out = tmp_path / "stamped.json"
        sys.path.insert(0, str(Path(BENCH).parent))
        try:
            import bench as bench_mod
        finally:
            sys.path.pop(0)
        old = bench_mod._JSON_OUT
        bench_mod._JSON_OUT = str(out)
        try:
            bench_mod._write_artifact({"value": 2.0})
        finally:
            bench_mod._JSON_OUT = old
        doc = json.loads(out.read_text())
        assert doc["bench_config"]["fingerprint"]
        assert doc["value"] == 2.0

    def test_duplicate_label_refused(self, tmp_path):
        hist = tmp_path / "hist.jsonl"
        a1 = self._artifact(tmp_path, "a1.json", 1.0, 1.0)
        assert (
            _bench(
                "--record", str(a1), "--label", "rX", "--history", str(hist),
                cwd=tmp_path,
            ).returncode
            == 0
        )
        r = _bench(
            "--record", str(a1), "--label", "rX", "--history", str(hist),
            cwd=tmp_path,
        )
        assert r.returncode != 0
        assert "already recorded" in r.stdout.decode()

    def test_trend_schema_check_rejects_malformed_store(self, tmp_path):
        hist = tmp_path / "hist.jsonl"
        hist.write_text('{"label": "r01"}\n')  # missing provenance keys
        r = _bench("--trend", "--history", str(hist), cwd=tmp_path)
        assert r.returncode != 0
        assert "missing" in r.stdout.decode()
        hist.write_text("not json\n")
        r = _bench("--trend", "--history", str(hist), cwd=tmp_path)
        assert r.returncode != 0

    def test_compare_one_arg_without_history_is_typed(self, tmp_path):
        a1 = self._artifact(tmp_path, "a1.json", 1.0, 1.0)
        r = _bench(
            "--compare", str(a1), "--history", str(tmp_path / "none.jsonl"),
            cwd=tmp_path,
        )
        assert r.returncode != 0
        assert "no trend store" in r.stdout.decode()

    def test_committed_history_round_trips(self):
        """The repo's own trend store (seeded with BENCH_r06 this PR)
        parses, trends, and one-arg-compares against its latest round."""
        repo = Path(BENCH).parent
        hist = repo / "BENCH_history.jsonl"
        assert hist.exists(), "BENCH_history.jsonl missing from the repo"
        r = _bench("--trend", cwd=repo)
        assert r.returncode == 0, r.stdout
        assert "rounds in" in r.stdout.decode()
