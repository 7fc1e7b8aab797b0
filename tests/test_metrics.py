"""Process-wide metrics registry tests: always-on counting with NO trace
active, snapshot/delta semantics, histogram accounting, Prometheus text
exposition, bump() dual-reporting, and the meta summary helper."""

import re

import numpy as np
import pytest

from parquet_tpu.core.reader import FileReader
from parquet_tpu.core.writer import FileWriter
from parquet_tpu.meta.parquet_types import Type
from parquet_tpu.schema.builder import message, required, string
from parquet_tpu.utils import metrics
from parquet_tpu.utils.trace import active, bump


@pytest.fixture(scope="module")
def sample(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("metrics") / "m.parquet")
    schema = message(required("id", Type.INT64), required("name", string()))
    with FileWriter(path, schema, codec="snappy") as w:
        w.write_column("id", np.arange(3000, dtype=np.int64))
        w.write_column("name", [f"n{i % 41}" for i in range(3000)])
    return path


class TestAlwaysOn:
    def test_plain_read_reports_pages_bytes_encodings(self, sample):
        """The acceptance bar: nonzero page/byte/encoding counters after a
        plain FileReader read with NO trace active."""
        assert not active()
        snap0 = metrics.snapshot()
        with FileReader(sample) as r:
            for i in range(r.num_row_groups):
                r.read_row_group(i)
        d = metrics.delta(snap0)
        page_keys = [k for k in d if k.startswith("pages_decoded_total")]
        assert page_keys and all(d[k] > 0 for k in page_keys)
        # encoding labels are real parquet encoding names
        assert any(
            'encoding="PLAIN"' in k or 'encoding="RLE_DICTIONARY"' in k
            or 'encoding="PLAIN_DICTIONARY"' in k
            for k in page_keys
        ), page_keys
        assert sum(
            v for k, v in d.items() if k.startswith("bytes_compressed_total")
        ) > 0
        assert sum(
            v for k, v in d.items() if k.startswith("bytes_uncompressed_total")
        ) > 0
        assert d.get("chunk_decode_seconds_count", 0) >= 2  # one per chunk
        assert d.get("chunk_decode_seconds_sum", 0) > 0

    def test_device_plan_read_also_reports(self, sample):
        snap0 = metrics.snapshot()
        with FileReader(sample, backend="tpu_roundtrip") as r:
            r.read_row_group(0)
        d = metrics.delta(snap0)
        assert any(k.startswith("pages_decoded_total") for k in d), d
        assert sum(
            v for k, v in d.items() if k.startswith("bytes_uncompressed_total")
        ) > 0


class TestSnapshotDelta:
    def test_counter_delta_exact(self):
        s0 = metrics.snapshot()
        metrics.inc("pqt_test_counter_total", 3, kind="x")
        metrics.inc("pqt_test_counter_total", 2, kind="x")
        d = metrics.delta(s0)
        assert d['pqt_test_counter_total{kind="x"}'] == 5

    def test_delta_omits_unchanged(self):
        metrics.inc("pqt_test_quiet_total", 1)
        s0 = metrics.snapshot()
        assert metrics.delta(s0) == {}

    def test_delta_skips_hist_min_max(self):
        s0 = metrics.snapshot()
        metrics.observe("pqt_test_seconds", 0.25)
        d = metrics.delta(s0)
        assert d["pqt_test_seconds_count"] == 1
        assert d["pqt_test_seconds_sum"] == pytest.approx(0.25)
        assert not any(
            k.startswith("pqt_test_seconds_min")
            or k.startswith("pqt_test_seconds_max")
            for k in d
        )

    def test_histogram_snapshot_min_max(self):
        metrics.observe("pqt_test_hist2", 0.5)
        metrics.observe("pqt_test_hist2", 1.5)
        s = metrics.snapshot()
        assert s["pqt_test_hist2_count"] >= 2
        assert s["pqt_test_hist2_min"] <= 0.5
        assert s["pqt_test_hist2_max"] >= 1.5

    def test_get(self):
        metrics.inc("pqt_test_get_total", 7, who="me")
        assert metrics.get("pqt_test_get_total", who="me") == 7
        assert metrics.get("pqt_test_get_total", who="nobody") == 0


class TestBumpDualReport:
    def test_bump_counts_without_trace(self):
        assert not active()
        before = metrics.get("events_total", event="pqt_test_event")
        bump("pqt_test_event")
        bump("pqt_test_event")
        assert metrics.get("events_total", event="pqt_test_event") == before + 2


class TestPrometheus:
    def test_exposition_format(self):
        metrics.inc("pqt_test_prom_total", 4, encoding="PLAIN")
        metrics.observe("pqt_test_prom_seconds", 0.02)
        text = metrics.render_prometheus()
        assert "# TYPE parquet_tpu_pqt_test_prom_total counter" in text
        assert 'parquet_tpu_pqt_test_prom_total{encoding="PLAIN"} ' in text
        assert "# TYPE parquet_tpu_pqt_test_prom_seconds histogram" in text
        assert 'le="+Inf"' in text
        assert text.endswith("\n")
        # every sample line is "name{labels} value" with a numeric value
        for line in text.splitlines():
            if line.startswith("#") or not line:
                continue
            m = re.match(r"^parquet_tpu_\S+ (\S+)$", line)
            assert m, line
            float(m.group(1))

    def test_histogram_bucket_counts_cumulative(self):
        metrics.observe("pqt_test_buckets", 0.0001)
        metrics.observe("pqt_test_buckets", 100.0)
        text = metrics.render_prometheus()
        lines = [
            line for line in text.splitlines() if "pqt_test_buckets_bucket" in line
        ]
        counts = [int(line.rsplit(" ", 1)[1]) for line in lines]
        assert counts == sorted(counts)  # cumulative
        assert counts[-1] >= 1  # +Inf sees everything... via _count below
        assert "parquet_tpu_pqt_test_buckets_count 2" in text


class TestExpositionGolden:
    """The exposition-correctness contract on a FRESH registry (the
    process-wide one accumulates across the test run): label values are
    escaped per the Prometheus text format, histogram `le` bounds render
    as plain decimals, and documented families carry `# HELP` lines."""

    def test_golden_document(self):
        reg = metrics.MetricsRegistry()
        reg.inc("io_retries_total", 2, reason='back\\slash"quote\nnewline')
        reg.set("pool_queue_depth", 3, pool="pqt-io")
        reg.observe("chunk_decode_seconds", 0.002)
        reg.observe("chunk_decode_seconds", 2.0)
        assert reg.render_prometheus() == (
            '# HELP parquet_tpu_io_retries_total failed source attempts absorbed by the retry ladder\n'
            '# TYPE parquet_tpu_io_retries_total counter\n'
            'parquet_tpu_io_retries_total{reason="back\\\\slash\\"quote\\nnewline"} 2\n'
            '# HELP parquet_tpu_pool_queue_depth tasks submitted to a pqt-* pool and not yet running\n'
            '# TYPE parquet_tpu_pool_queue_depth gauge\n'
            'parquet_tpu_pool_queue_depth{pool="pqt-io"} 3\n'
            '# HELP parquet_tpu_chunk_decode_seconds per-chunk decode wall time\n'
            '# TYPE parquet_tpu_chunk_decode_seconds histogram\n'
            'parquet_tpu_chunk_decode_seconds_bucket{le="0.0005"} 0\n'
            'parquet_tpu_chunk_decode_seconds_bucket{le="0.001"} 0\n'
            'parquet_tpu_chunk_decode_seconds_bucket{le="0.005"} 1\n'
            'parquet_tpu_chunk_decode_seconds_bucket{le="0.01"} 1\n'
            'parquet_tpu_chunk_decode_seconds_bucket{le="0.05"} 1\n'
            'parquet_tpu_chunk_decode_seconds_bucket{le="0.1"} 1\n'
            'parquet_tpu_chunk_decode_seconds_bucket{le="0.5"} 1\n'
            'parquet_tpu_chunk_decode_seconds_bucket{le="1"} 1\n'
            'parquet_tpu_chunk_decode_seconds_bucket{le="5"} 2\n'
            'parquet_tpu_chunk_decode_seconds_bucket{le="+Inf"} 2\n'
            'parquet_tpu_chunk_decode_seconds_sum 2.002\n'
            'parquet_tpu_chunk_decode_seconds_count 2\n'
        )

    def test_label_escaping_round_trips(self):
        """An escaped sample line still parses: unescaping recovers the
        original value exactly (what a scraper's parser will do)."""
        reg = metrics.MetricsRegistry()
        hostile = 'a\\b"c\nd\\\\e""'
        reg.inc("pqt_test_escape_total", 1, v=hostile)
        [line] = [
            ln for ln in reg.render_prometheus().splitlines()
            if ln.startswith("parquet_tpu_pqt_test_escape_total")
        ]
        assert "\n" not in line  # the raw newline would split the sample
        quoted = line[line.index('v="') + 3 : line.rindex('"')]
        unescaped = (
            quoted.replace("\\\\", "\x00")
            .replace('\\"', '"')
            .replace("\\n", "\n")
            .replace("\x00", "\\")
        )
        assert unescaped == hostile

    def test_le_bounds_never_scientific(self):
        """repr() would render tight bounds as 5e-05; the exposition must
        print plain decimals for every bound."""
        h = metrics._Hist(buckets=(0.00005, 0.5, 1.0, 10.0))
        reg = metrics.MetricsRegistry()
        reg._hists[("pqt_test_le_seconds", ())] = h
        text = reg.render_prometheus()
        assert 'le="0.00005"' in text
        assert 'le="1"' in text and 'le="1.0"' not in text
        assert 'le="10"' in text
        assert "e-" not in text.lower().replace('le="+inf"', "")

    def test_help_precedes_type_once_per_family(self):
        reg = metrics.MetricsRegistry()
        reg.inc("io_retries_total", 1, reason="eio")
        reg.inc("io_retries_total", 1, reason="short_read")
        lines = reg.render_prometheus().splitlines()
        help_ix = [i for i, ln in enumerate(lines) if ln.startswith("# HELP")]
        assert len(help_ix) == 1  # one HELP per family, not per sample
        assert lines[help_ix[0] + 1].startswith(
            "# TYPE parquet_tpu_io_retries_total"
        )

    def test_undocumented_family_renders_without_help(self):
        reg = metrics.MetricsRegistry()
        reg.inc("pqt_test_undoc_total", 1)
        text = reg.render_prometheus()
        assert "# HELP" not in text
        assert "# TYPE parquet_tpu_pqt_test_undoc_total counter" in text


class TestOpenMetricsGolden:
    """The content-negotiated OpenMetrics variant: counter families drop
    their _total suffix in # TYPE while samples keep it, histogram bucket
    samples carry exemplars in the spec's ` # {labels} value ts` syntax,
    the document terminates with # EOF — and the CLASSIC exposition stays
    byte-for-byte unchanged for existing scrapers."""

    def _reg(self):
        reg = metrics.MetricsRegistry()
        reg.inc("io_retries_total", 2, reason="eio")
        reg.set("pool_queue_depth", 3, pool="pqt-io")
        reg.observe(
            "serve_request_seconds",
            0.26,
            exemplar={"request_id": "abc123"},
            endpoint="/v1/scan",
        )
        return reg

    def test_counter_family_drops_total_suffix(self):
        om = self._reg().render_openmetrics()
        assert "# TYPE parquet_tpu_io_retries counter" in om
        assert 'parquet_tpu_io_retries_total{reason="eio"} 2' in om
        # the classic format keeps the full name in TYPE
        classic = self._reg().render_prometheus()
        assert "# TYPE parquet_tpu_io_retries_total counter" in classic

    def test_document_terminates_with_eof(self):
        om = self._reg().render_openmetrics()
        assert om.endswith("# EOF\n")
        assert om.count("# EOF") == 1

    def test_exemplar_rides_the_canonical_bucket_only(self):
        om = self._reg().render_openmetrics()
        ex_lines = [ln for ln in om.splitlines() if " # {" in ln]
        assert len(ex_lines) == 1
        [line] = ex_lines
        # 0.26 lands in the le="0.5" bucket (its first admitting bound)
        assert 'le="0.5"' in line
        sample, _, exemplar = line.partition(" # ")
        assert sample.endswith(" 1")
        labels, _, rest = exemplar.partition("} ")
        assert labels == '{request_id="abc123"'
        value, ts = rest.split(" ")
        assert float(value) == 0.26
        assert float(ts) > 0  # unix timestamp, spec-optional but emitted

    def test_exemplar_label_values_escape(self):
        reg = metrics.MetricsRegistry()
        reg.observe(
            "serve_request_seconds",
            0.002,
            exemplar={"request_id": 'a"b\\c\nd'},
            endpoint="/v1/plan",
        )
        om = reg.render_openmetrics()
        [line] = [ln for ln in om.splitlines() if " # {" in ln]
        assert '{request_id="a\\"b\\\\c\\nd"}' in line
        assert "\n" not in line  # the raw newline would split the sample

    def test_classic_format_is_unchanged_by_exemplars(self):
        """An existing scraper must see identical bytes whether or not
        exemplars were ever attached."""
        with_ex = self._reg()
        without = metrics.MetricsRegistry()
        without.inc("io_retries_total", 2, reason="eio")
        without.set("pool_queue_depth", 3, pool="pqt-io")
        without.observe("serve_request_seconds", 0.26, endpoint="/v1/scan")
        assert with_ex.render_prometheus() == without.render_prometheus()
        classic = with_ex.render_prometheus()
        assert "# EOF" not in classic and " # {" not in classic

    def test_histograms_and_gauges_render_in_openmetrics(self):
        om = self._reg().render_openmetrics()
        assert "# TYPE parquet_tpu_pool_queue_depth gauge" in om
        assert "# TYPE parquet_tpu_serve_request_seconds histogram" in om
        assert (
            'parquet_tpu_serve_request_seconds_bucket{endpoint="/v1/scan",le="+Inf"} 1'
            in om
        )
        assert 'parquet_tpu_serve_request_seconds_count{endpoint="/v1/scan"} 1' in om

    def test_module_render_refreshes_uptime_gauge(self):
        text = metrics.render_prometheus()
        assert "parquet_tpu_process_uptime_seconds" in text
        assert "# TYPE parquet_tpu_process_uptime_seconds gauge" in text
        up = metrics.get("process_uptime_seconds")
        assert up >= 0
        om = metrics.render_openmetrics()
        assert "parquet_tpu_process_uptime_seconds" in om


class TestGauges:
    def test_set_last_write_wins(self):
        metrics.set_gauge("pqt_test_gauge", 3)
        metrics.set_gauge("pqt_test_gauge", 1)
        assert metrics.get("pqt_test_gauge") == 1
        assert metrics.snapshot()["pqt_test_gauge"] == 1

    def test_labeled_gauges_are_independent(self):
        metrics.set_gauge("pqt_test_gauge_lbl", 2, lane="a")
        metrics.set_gauge("pqt_test_gauge_lbl", 5, lane="b")
        assert metrics.get("pqt_test_gauge_lbl", lane="a") == 2
        assert metrics.get("pqt_test_gauge_lbl", lane="b") == 5

    def test_exposition_declares_gauge_type(self):
        metrics.set_gauge("pqt_test_gauge_expo", 7)
        text = metrics.render_prometheus()
        assert "# TYPE parquet_tpu_pqt_test_gauge_expo gauge" in text
        assert "parquet_tpu_pqt_test_gauge_expo 7" in text

    def test_delta_skips_gauges(self):
        snap = metrics.snapshot()
        metrics.set_gauge("pqt_test_gauge_delta", 42)
        metrics.inc("pqt_test_gauge_sibling_counter")
        d = metrics.delta(snap)
        assert "pqt_test_gauge_delta" not in d  # non-monotonic: no diff
        assert d.get("pqt_test_gauge_sibling_counter") == 1


class TestReportAndSummary:
    def test_human_report(self, sample):
        with FileReader(sample) as r:
            r.read_row_group(0)
        text = metrics.report()
        assert "pages decoded" in text
        assert "compression ratio" in text

    def test_summarize_columns(self, sample):
        with FileReader(sample) as r:
            s = metrics.summarize_columns(r.metadata)
        assert set(s) == {"id", "name"}
        for col in s.values():
            assert col["compressed"] > 0
            assert col["uncompressed"] > 0
            assert col["ratio"] is not None and col["ratio"] > 0
            assert col["encodings"]


class TestFinalizersUnderTheLocks:
    """An allocation under a lock can start a garbage collection, and a
    finalizer run by it on the same thread may report metrics (an abandoned
    ParquetDataset iterator's `finally` drops its in-flight gauge and
    cancels its futures). The locks such a finalizer reaches must be
    re-entrant: with plain Locks the thread deadlocks on itself and every
    other thread behind it — seen as a hung test run."""

    @pytest.mark.parametrize("which", ["registry", "pool", "dataset_inflight"])
    def test_a_finalizer_reporting_metrics_does_not_deadlock(self, which):
        import threading

        from parquet_tpu.data import dataset as dataset_mod
        from parquet_tpu.obs import pool as pool_mod

        lock = {
            "registry": metrics.REGISTRY._lock,
            "pool": pool_mod._lock,
            "dataset_inflight": dataset_mod._inflight_lock,
        }[which]

        class Reporter:
            def __del__(self):  # what the iterator's `finally` does
                dataset_mod._inflight_add(0)
                pool_mod._adjust("pqt-test-finalizer", dq=+1)
                pool_mod._adjust("pqt-test-finalizer", dq=-1)
                metrics.inc("finalizer_reports_total")

        done = threading.Event()

        def body():
            obj = Reporter()
            with lock:
                del obj  # the finalizer runs here, on this thread, under the lock
            done.set()

        before = metrics.snapshot().get("finalizer_reports_total", 0)
        t = threading.Thread(target=body, daemon=True)
        t.start()
        t.join(timeout=10)
        assert done.is_set(), f"deadlocked under the {which} lock"
        assert metrics.snapshot()["finalizer_reports_total"] == before + 1
