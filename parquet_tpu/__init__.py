"""parquet_tpu — a TPU-native Apache Parquet framework.

A brand-new implementation of the capability set of fraugster/parquet-go
(see SURVEY.md), designed TPU-first: file I/O, Thrift metadata, block
decompression, and record assembly run on the host; the column-decode hot path
(RLE/bit-packing hybrid, dictionary lookup, delta-binary-packed) runs as batched
JAX/Pallas kernels behind a pluggable decoder backend
(FileReader.read_row_group_device(); host-bound reads always decode on host).

Quick start:

    import parquet_tpu as pq

    # read
    with pq.FileReader("f.parquet") as r:
        cols = r.read_row_group(0)                  # columnar arrays
        rows = list(r.iter_rows())                  # assembled records

    # write
    schema = pq.parse_schema("message m { required int64 id; }")
    with pq.FileWriter("out.parquet", schema, codec="snappy") as w:
        w.write_row({"id": 1})

    # high-level dataclass mapping
    from parquet_tpu import floor

Layout:
  meta/      Thrift compact protocol + parquet-format metadata model
  ops/       host (NumPy-vectorized) encoders/decoders — the correctness oracle
  kernels/   device (JAX/XLA + Pallas) decode ops + the batched page pipeline
  core/      pages, chunks, column stores, schema tree, FileReader/FileWriter
  io/        pluggable byte sources (lock-free local pread, in-memory,
             retrying remote-shaped), footer-driven range planning with
             coalescing + readahead, block/footer caches
  sink/      pluggable byte sinks (atomic tmp+rename local files,
             in-memory, write-combining buffer) + the parallel row-group
             encode pipeline on the pqt-encode pool
  data/      streaming dataset: sharded/shuffled multi-file plans, bounded
             prefetch, fixed-size rebatching, mid-epoch checkpoint/resume
  serve/     the scan/query daemon: typed HTTP protocol, warm-cache
             planning, streaming push-down execution, admission control
  schema/    textual schema DSL (parser/printer/validator) + builder API
  floor/     high-level record marshal/unmarshal + dataclass autoschema
  parallel/  shard_map/mesh scale-out over pages, columns, and row groups
  tools/     parquet-tool and csv2parquet CLIs
  utils/     native C++ helpers (snappy, scans), varints, INT96 time
  native/    the C++ helper library (build with `make -C native`)
"""

__version__ = "0.1.0"

from .core.packing import PackedBatch  # noqa: F401
from .core.reader import FileReader, MaskedColumn, RaggedColumn  # noqa: F401
from .ops.packed_levels import PackedLevels  # noqa: F401
from .core.writer import FileWriter, WriterError  # noqa: F401
from .core.schema import Column, Schema, SchemaError  # noqa: F401
from .core.arrays import ByteArrayData  # noqa: F401
from .core.alloc import AllocError  # noqa: F401
from .core.filter import FilterError  # noqa: F401
from .core.compress import register_codec, CompressionError  # noqa: F401
from .core.merge import merge_files, split_row_groups  # noqa: F401
from .meta import (  # noqa: F401
    CompressionCodec,
    ConvertedType,
    Encoding,
    FieldRepetitionType,
    LogicalType,
    PageType,
    ParquetFileError,
    Type,
    read_file_metadata,
)
from .schema.dsl import (  # noqa: F401
    SchemaParseError,
    parse_schema,
    schema_to_string,
    validate,
    validate_strict,
)
from .schema import builder  # noqa: F401
from . import floor  # noqa: F401
from .data import ParquetDataset  # noqa: F401  (host-only at import; jax lazy)
from .io import (  # noqa: F401
    BlockCache,
    ByteSource,
    FooterCache,
    HttpSource,
    LocalFileSource,
    MemorySource,
    ObjectStoreSource,
    RetryingSource,
    SourceError,
    TieredCache,
    TransientSourceError,
)
from .sink import (  # noqa: F401
    BufferedSink,
    ByteSink,
    FileObjectSink,
    LocalFileSink,
    MemorySink,
    SinkError,
)


def __getattr__(name):
    # `parallel` imports jax (and flips jax_enable_x64) at module load; keep
    # that out of the base import path — pure host read/write must work
    # without jax, and backend init can be slow on experimental platforms.
    if name == "parallel":
        import importlib

        module = importlib.import_module(".parallel", __name__)
        globals()["parallel"] = module
        return module
    if name == "serve":
        # the daemon layer is stdlib-only but pulls http.server machinery
        # nothing but `parquet-tool serve`/embedders need — keep it lazy
        import importlib

        module = importlib.import_module(".serve", __name__)
        globals()["serve"] = module
        return module
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
