"""Bring-up contracts: what must hold for the program to run on the chip.

  * `chip_smoke.py` rehearsed on the CPU (asked for by name) passes every leg
    and says it is a rehearsal; without the name, a machine with no TPU fails
    before any set-up work and prints no result;
  * the compile cache sits where JAX_COMPILATION_CACHE_DIR says, else at one
    fixed path inside the checkout;
  * the native artifacts are build outputs: get_native() rebuilds a stale one,
    and processes racing to do so all load a whole library;
  * a device that cannot hold float64 bit-exactly (any TPU) refuses DOUBLE
    with a typed error on every road into device memory;
  * `serve --device` / bench device phases refuse a non-TPU default device
    unless JAX_PLATFORMS names cpu outright.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
SMOKE = str(ROOT / "chip_smoke.py")


def _run(cmd, env=None, timeout=600):
    return subprocess.run(
        cmd, cwd=str(ROOT), env=env, timeout=timeout,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )


class TestChipSmoke:
    def test_cpu_rehearsal_passes_and_says_so(self, tmp_path):
        r = _run(
            [sys.executable, SMOKE, "--platform", "cpu", "--rows", "262144",
             "--workdir", str(tmp_path)]
        )
        assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
        assert "REHEARSAL" in r.stdout
        lines = r.stdout.strip().splitlines()
        # the last line is the driver's verdict: exactly these keys
        verdict = json.loads(lines[-1])
        assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
        assert set(verdict["device"]) == {"platform", "kind", "count"}
        assert isinstance(verdict["device"]["count"], int)
        (tagged,) = [l for l in lines if l.startswith("smoke: summary ")]
        summary = json.loads(tagged[len("smoke: summary "):])
        assert summary["ok"] is True and summary["device"] == verdict["device"]
        assert summary["mode"] == "rehearsal, cpu"
        # (count follows the virtual CPU mesh conftest asks XLA for)
        assert (summary["device"]["platform"], summary["device"]["kind"]) == ("cpu", "cpu")
        assert set(summary["legs"].values()) == {"passed"}
        d = summary["decode"]
        assert d["prepare_fused_engaged"] == d["chunks"] > 0
        assert all(d["host_decoded_pages"][c] == 0 for c in ("trip_id", "vendor", "ts", "passenger_count"))
        assert d["compile_warm"]["requests"] == 0
        assert summary["filter"]["device_filter_engaged"] > 0
        assert summary["kernels"]["device_write_engaged"] == 4
        units = summary["daemon"]["query_device_units"]
        assert units["device"] > 0 and units["host_fallback"] == summary["daemon"]["query_units"][2]
        assert summary["claim"] is None

    def test_no_chip_fails_before_setup_and_prints_no_result(self, tmp_path):
        # the sandbox pins jax to the CPU through the environment: that is
        # not a rehearsal request, and the run must fail on what jax finds
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = _run([sys.executable, SMOKE, "--workdir", str(tmp_path)], env=env)
        assert r.returncode != 0
        assert "needs 'tpu'" in r.stderr
        assert not any(line.startswith("{") for line in r.stdout.splitlines())
        assert not list(tmp_path.iterdir())  # no corpus was written


class TestCompileCachePlacement:
    CODE = (
        "import parquet_tpu.kernels.device_ops as d, jax; "
        "print(jax.config.jax_compilation_cache_dir); print(d.COMPILE_CACHE_DIR); "
        "print(jax.config.jax_persistent_cache_min_compile_time_secs, "
        "jax.config.jax_persistent_cache_min_entry_size_bytes)"
    )

    def _probe(self, **extra):
        env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
        env.update(JAX_PLATFORMS="cpu", **extra)
        r = _run([sys.executable, "-c", self.CODE], env=env)
        assert r.returncode == 0, r.stderr[-2000:]
        return r.stdout.split()

    def test_environment_places_the_cache(self, tmp_path):
        used, _fixed, secs, size = self._probe(JAX_COMPILATION_CACHE_DIR=str(tmp_path))
        assert used == str(tmp_path)
        # sub-second programs are cached in an externally placed cache too
        assert (float(secs), int(size)) == (0.0, -1)

    def test_fixed_path_inside_the_checkout_otherwise(self):
        used, fixed, secs, size = self._probe()
        assert used == fixed == str(ROOT / ".jax_cache")
        assert (float(secs), int(size)) == (0.0, -1)


class TestNativeBuildOnLoad:
    def test_stale_artifact_rebuilds_and_racing_processes_all_load(self):
        from parquet_tpu.utils import native

        lib = native._LIB_PATH
        newest_source = max(
            (native._NATIVE_DIR / s).stat().st_mtime_ns for s in native._SOURCES
        )
        assert native.require_native().fused_gil_free
        os.utime(lib, ns=(1, 1))  # older than every source
        code = (
            "from parquet_tpu.utils.native import require_native; l = require_native(); "
            "d = b'abc' * 1000; assert l.snappy_decompress(l.snappy_compress(d), len(d)) == d; "
            "print(l.fused_gil_free)"
        )
        procs = [
            subprocess.Popen([sys.executable, "-c", code], cwd=str(ROOT),
                             stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
            for _ in range(3)
        ]
        outs = [p.communicate(timeout=300) for p in procs]
        assert [p.returncode for p in procs] == [0, 0, 0], outs
        assert [o[0].strip() for o in outs] == ["True"] * 3
        assert lib.stat().st_mtime_ns >= newest_source
        # built under a private name and renamed: nothing half-written left
        assert sorted(p.name for p in lib.parent.iterdir()) == [".lock", lib.name]

    def test_no_binary_is_tracked(self):
        r = _run(["git", "ls-files"])
        if r.returncode != 0:
            pytest.skip("not a git checkout")
        assert not [f for f in r.stdout.splitlines() if f.endswith(".so")]


class TestDoubleRefusal:
    @pytest.fixture
    def doubles_file(self, tmp_path):
        import pyarrow as pa
        import pyarrow.parquet as pq

        p = str(tmp_path / "d.parquet")
        rng = np.random.default_rng(0)
        pq.write_table(
            pa.table({"a": np.arange(4000, dtype=np.int64), "x": rng.random(4000),
                      "y": pa.array(np.round(rng.random(4000), 1))}),
            p, use_dictionary=["y"],
        )
        return p

    def test_exact_device_delivers_doubles_bit_for_bit(self, doubles_file):
        import pyarrow.parquet as pq

        from parquet_tpu import FileReader

        with FileReader(doubles_file) as r:
            g = r.read_row_group_device(0)
        ref = pq.read_table(doubles_file)
        for name in ("x", "y"):
            got = np.asarray(g[(name,)].values)
            assert got.dtype == np.float64
            assert np.array_equal(got.view(np.uint64), ref[name].to_numpy().view(np.uint64))

    def test_inexact_device_refuses_typed_on_every_road(self, doubles_file, monkeypatch):
        import jax

        from parquet_tpu import FileReader, ParquetDataset, ParquetFileError
        from parquet_tpu.kernels import pipeline
        from parquet_tpu.serve.server import ScanService, ServeConfig
        from parquet_tpu.serve.protocol import parse_query_request
        from parquet_tpu.utils import metrics

        # what a TPU measures: float64 does not come back bit-identical
        monkeypatch.setattr(pipeline, "_platform_holds_f64", lambda platform: False)
        assert issubclass(pipeline.DeviceDoubleError, ParquetFileError)
        with FileReader(doubles_file) as r:
            for cols in (["x"], ["y"], None):
                with pytest.raises(pipeline.DeviceDoubleError, match="bit-exactly"):
                    r.read_row_group_device(0, cols)
            with pytest.raises(pipeline.DeviceDoubleError):
                next(iter(r.iter_device_batches(100, columns=["a", "x"])))
            assert ("a",) in r.read_row_group_device(0, ["a"])  # ints unaffected
        with pytest.raises(pipeline.DeviceDoubleError):
            next(iter(ParquetDataset(doubles_file, batch_size=100, device=jax.devices()[0])))
        # the daemon's device route declines such a unit to the host, counted
        svc = ScanService(ServeConfig(root=os.path.dirname(doubles_file), device=True))
        q = parse_query_request(json.dumps({
            "paths": "d.parquet", "filters": [["x", ">", 0.5]],
            "aggregates": ["count", ["sum", "a"]],
        }).encode())
        snap = metrics.snapshot()
        ticket, body = svc.query(q, "t")
        ticket.release()
        d = metrics.delta(snap)
        assert d.get('query_device_units_total{engine="host_fallback"}') == 1
        assert not d.get('query_device_units_total{engine="device"}')
        x = np.random.default_rng(0).random(4000)
        assert body["result"]["count"] == int((x > 0.5).sum())


class TestRequireChip:
    def test_refuses_a_cpu_default_unless_named(self, monkeypatch):
        from parquet_tpu.kernels.device_ops import device_facts, require_chip

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert require_chip() == device_facts()
        assert device_facts()["platform"] == "cpu"
        for value in ("tpu,cpu", ""):
            monkeypatch.setenv("JAX_PLATFORMS", value)
            with pytest.raises(RuntimeError, match="needs a TPU"):
                require_chip()

    def test_serve_device_flag_reports_its_device(self, tmp_path):
        import re
        import signal
        import urllib.request

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        p = subprocess.Popen(
            [sys.executable, "-m", "parquet_tpu.tools.parquet_tool", "serve",
             "--device", "--root", str(tmp_path), "--port", "0"],
            cwd=str(ROOT), env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        try:
            url = None
            for line in p.stdout:
                if m := re.search(r"listening on (http://\S+)", line):
                    url = m.group(1)
                if line.startswith("serve: device "):
                    assert line.startswith("serve: device cpu 'cpu' id 0 of ")
                    break
            with urllib.request.urlopen(url + "/healthz", timeout=30) as resp:
                health = json.loads(resp.read())
            assert health["device"]["platform"] == "cpu"
            p.send_signal(signal.SIGTERM)
            assert "serve: drained, bye" in p.stdout.read()
            assert p.wait(timeout=60) == 0
        finally:
            if p.poll() is None:
                p.kill()
                p.wait()
