"""dcr-search: LAION pipeline (reference embedding_search/ scripts) plus
the dcr-store sharded-store workflow.

Subcommands:
    download  --parquet_path=... --laion_folder=...
    embed     --gen_folder=<images-or-tars-dir> [--embedding_out=...]
    search    --gen_folder=... --laion_folder=<dir-of-chunk-dirs> --out_path=...
              [--store_dir=<built store>]   # store-backed instead of brute force
    build     --store_dir=... --laion_folder=<dir-of-chunk-dirs> [--dumps=a.npz,b.pkl]
              [--shard_rows=N] [--store_normalize=true]
    append    --store_dir=... --laion_folder=... [--dumps=...]
    verify    --store_dir=...            # read-only; exit 1 on corrupt shards
    query     --store_dir=... --gen_folder=... --out_path=... [--top_k=K]
              [--query_batch=B] [--segment_rows=R] [--warm_dir=...]
              [--live=true]              # include the WAL live tail (dcr-live)
              [--ann=true --nprobe=N]    # IVF tier instead of exact scan
    recover   --store_dir=...            # replay the WAL: truncate torn
                                         # tails, reload acked rows, print
                                         # the recovery report
    compact   --store_dir=...            # recover, then fold the WAL into
                                         # committed shards + new snapshot
                                         # (+ incremental IVF list folds)
    train-ivf --store_dir=... [--n_lists=L] [--ivf_iters=I] [--ivf_seed=S]
              [--ivf_train_rows=N] [--ivf_normalize=true] [--warm_dir=...]
                                         # train the IVF quantizer + commit
                                         # int8 inverted lists (dcr-ann)
    stats     --store_dir=... [--json_out=true]
                                         # committed + live + ann tier
                                         # summary for fleet runbooks
"""

from __future__ import annotations

import json
import logging
import sys
from pathlib import Path

from dcr_tpu.core.config import SearchConfig, parse_cli
from dcr_tpu.search import embed as E
from dcr_tpu.search import search as S

USAGE = ("usage: dcr-search {download|embed|search|build|append|verify|query"
         "|recover|compact|train-ivf|stats} --key=value ...")


def _store_sources(cfg: SearchConfig) -> list:
    sources = [Path(p) for p in cfg.dumps]
    if cfg.laion_folder:
        sources.append(Path(cfg.laion_folder))
    if not sources:
        raise SystemExit(
            "build/append needs --laion_folder=<dir> and/or --dumps=<files>")
    return sources


def _cmd_build(cfg: SearchConfig, append: bool) -> None:
    from dcr_tpu.search.store import EmbeddingStoreWriter, ingest_dumps

    if not cfg.store_dir:
        raise SystemExit("build/append needs --store_dir=<dir>")
    writer = (EmbeddingStoreWriter.append(cfg.store_dir) if append
              else EmbeddingStoreWriter.create(
                  cfg.store_dir, shard_rows=cfg.shard_rows,
                  normalize=cfg.store_normalize))
    report = ingest_dumps(writer, _store_sources(cfg))
    print(json.dumps(report, indent=1, sort_keys=True))


def _cmd_verify(cfg: SearchConfig) -> None:
    from dcr_tpu.search.store import EmbeddingStoreReader

    if not cfg.store_dir:
        raise SystemExit("verify needs --store_dir=<dir>")
    # read-only on purpose: inspecting a possibly-shared store must not
    # quarantine-rename anything out from under its other readers
    reader = EmbeddingStoreReader(cfg.store_dir, quarantine=False)
    report = reader.verify()
    print(json.dumps(report, indent=1, sort_keys=True))
    if report["corrupt"]:
        raise SystemExit(1)


def _cmd_query(cfg: SearchConfig) -> None:
    if not cfg.store_dir:
        raise SystemExit("query needs --store_dir=<dir>")
    out = S.run_search(cfg)
    print(f"search results -> {out}")


def _cmd_recover(cfg: SearchConfig, compact: bool) -> None:
    """Take the writer lease, replay the WAL (truncating torn tails), and
    with ``compact`` fold the recovered tail into committed shards and
    publish the next snapshot — the manual form of what a restarted
    ingesting worker does on open."""
    from dcr_tpu.search.livestore import LiveStore

    if not cfg.store_dir:
        raise SystemExit("recover/compact needs --store_dir=<dir>")
    with LiveStore.open(cfg.store_dir) as live:
        report = live.report()
        if compact:
            report["compaction"] = live.compact()
    print(json.dumps(report, indent=1, sort_keys=True))


def _cmd_train_ivf(cfg: SearchConfig) -> None:
    from dcr_tpu.search import ann

    if not cfg.store_dir:
        raise SystemExit("train-ivf needs --store_dir=<built store>")
    report = ann.train_ivf(
        cfg.store_dir, n_lists=cfg.n_lists, iters=cfg.ivf_iters,
        seed=cfg.ivf_seed, train_rows=cfg.ivf_train_rows,
        normalize=cfg.ivf_normalize, warm_dir=cfg.warm_dir)
    print(json.dumps(report, indent=1, sort_keys=True))


def store_stats(store_dir: str) -> dict:
    """Committed + live + ann tier summary (read-only, never quarantines)
    — the ``dcr-search stats`` payload, importable for tests/runbooks."""
    from dcr_tpu.search import ann
    from dcr_tpu.search.store import read_store_manifest

    manifest = read_store_manifest(Path(store_dir), quarantine=False)
    report: dict = {"store_dir": str(store_dir), "committed": {
        "snapshot": int(manifest.get("snapshot", 0)),
        "rows": int(manifest["total"]),
        "shards": len(manifest["shards"]),
        "shard_rows": int(manifest["shard_rows"]),
        "embed_dim": int(manifest["embed_dim"]),
        "normalized": bool(manifest.get("normalized", False)),
        "wal_through": int(manifest.get("wal_through", 0)),
    }}
    try:
        from dcr_tpu.search.livestore import load_wal_tail

        feats, _keys, wal = load_wal_tail(store_dir)
        report["live"] = {"tail_rows": int(feats.shape[0]),
                          "records": int(wal.get("records", 0)),
                          "torn_segments": int(wal.get("torn_segments", 0))}
    except Exception:
        report["live"] = {"tail_rows": 0, "records": 0, "torn_segments": 0}
    report["ann"] = ann.ann_stats(store_dir)
    return report


def _cmd_stats(cfg: SearchConfig) -> None:
    if not cfg.store_dir:
        raise SystemExit("stats needs --store_dir=<dir>")
    report = store_stats(cfg.store_dir)
    if cfg.json_out:
        print(json.dumps(report, indent=1, sort_keys=True))
        return
    c = report["committed"]
    print(f"store      {report['store_dir']}")
    print(f"committed  {c['rows']} rows in {c['shards']} shard(s) "
          f"(snapshot v{c['snapshot']}, shard_rows={c['shard_rows']}, "
          f"dim={c['embed_dim']}, "
          f"{'normalized' if c['normalized'] else 'raw'}, "
          f"wal_through={c['wal_through']})")
    lv = report["live"]
    print(f"live       {lv['tail_rows']} uncompacted WAL row(s) in "
          f"{lv['records']} record(s), {lv['torn_segments']} torn")
    a = report["ann"]
    if a is None:
        print("ann        (none — run `dcr-search train-ivf`)")
    else:
        print(f"ann        {a['rows']} rows in {a['nonempty_lists']}/"
              f"{a['n_lists']} lists (snapshot v{a['snapshot']}, "
              f"{a['quantization']}, "
              f"{'normalized' if a['normalized'] else 'raw'}, "
              f"max list {a['max_list_rows']} rows, seed={a['seed']})")


def main(argv=None) -> None:
    from dcr_tpu.cli import setup_compile_cache

    setup_compile_cache()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0].startswith("--"):
        raise SystemExit(USAGE)
    command, rest = argv[0], argv[1:]
    cfg = parse_cli(SearchConfig, rest)
    if cfg.logdir:
        from dcr_tpu.core import tracing

        tracing.configure(cfg.logdir)
    if command == "download":
        E.download_laion_chunk(cfg.parquet_path, cfg.laion_folder,
                               image_size=cfg.image_size)
        E.embed_images(cfg, source=cfg.laion_folder)
        if cfg.delete_tars:
            E.cleanup_tars(cfg.laion_folder)
    elif command == "embed":
        E.embed_images(cfg, source=cfg.gen_folder,
                       out_path=cfg.embedding_out or None)
    elif command == "search":
        folders = ()
        if not cfg.store_dir:
            folders = sorted(p for p in Path(cfg.laion_folder).iterdir()
                             if p.is_dir())
        S.run_search(cfg, laion_folders=folders)
    elif command == "build":
        _cmd_build(cfg, append=False)
    elif command == "append":
        _cmd_build(cfg, append=True)
    elif command == "verify":
        _cmd_verify(cfg)
    elif command == "query":
        _cmd_query(cfg)
    elif command == "recover":
        _cmd_recover(cfg, compact=False)
    elif command == "compact":
        _cmd_recover(cfg, compact=True)
    elif command == "train-ivf":
        _cmd_train_ivf(cfg)
    elif command == "stats":
        _cmd_stats(cfg)
    else:
        raise SystemExit(f"unknown subcommand {command!r}")


if __name__ == "__main__":
    main()
