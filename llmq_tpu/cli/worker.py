"""Worker launchers (reference: llmq/cli/worker.py:9-250)."""

from __future__ import annotations

import asyncio
import sys
from typing import Optional

import click

from llmq_tpu.core.pipeline import load_pipeline_config
from llmq_tpu.utils.logging import setup_logging


def build_tpu_worker(
    model: str,
    queue: str,
    *,
    tensor_parallel: Optional[int] = None,
    data_parallel: int = 1,
    sequence_parallel: int = 1,
    concurrency: Optional[int] = None,
    max_num_seqs: Optional[int] = None,
    max_model_len: Optional[int] = None,
    dtype: str = "bfloat16",
    kv_dtype: Optional[str] = None,
    prefill_chunk_size: Optional[int] = None,
    enable_prefix_caching: bool = False,
    prefix_host_gb: Optional[float] = None,
    decode_block: Optional[int] = None,
    spec_tokens: Optional[int] = None,
    tp_overlap: Optional[str] = None,
    mixed_step: Optional[str] = None,
    role: Optional[str] = None,
):
    """The TPU inference worker exactly as ``llmq-tpu worker run`` builds
    it (reference run_vllm_worker) — also what ``chip_smoke.py`` runs, so
    the smoke and the CLI cannot drift apart."""
    setup_logging(structured=True)
    if role is not None:
        # Role rides Config (LLMQ_WORKER_ROLE) so the broker manager and
        # worker base read one consistent value; the flag just pins the
        # env before the worker builds its config.
        import os

        os.environ["LLMQ_WORKER_ROLE"] = role
    try:
        from llmq_tpu.workers.tpu_worker import TPUWorker
    except ImportError as exc:
        click.echo(f"TPU worker unavailable: {exc}", err=True)
        sys.exit(1)
    click.echo(
        f"Starting TPU worker: model={model} queue={queue}"
        + (f" role={role}" if role else ""),
        err=True,
    )
    return TPUWorker(
        queue,
        model=model,
        tensor_parallel=tensor_parallel,
        data_parallel=data_parallel,
        sequence_parallel=sequence_parallel,
        concurrency=concurrency,
        max_num_seqs=max_num_seqs,
        max_model_len=max_model_len,
        dtype=dtype,
        kv_dtype=kv_dtype,
        prefill_chunk_size=prefill_chunk_size,
        enable_prefix_caching=enable_prefix_caching,
        prefix_host_gb=prefix_host_gb,
        decode_block=decode_block,
        spec_tokens=spec_tokens,
        tp_overlap=tp_overlap,
        mixed_step=mixed_step,
    )


def run_tpu_worker(model: str, queue: str, **options) -> None:
    """Launch the TPU inference worker and serve until stopped."""
    _run(build_tpu_worker(model, queue, **options))


def run_dummy_worker(
    queue: str, *, concurrency: Optional[int] = None, delay: float = 1.0
) -> None:
    setup_logging(structured=True)
    from llmq_tpu.workers.dummy import DummyWorker

    click.echo(f"Starting dummy worker on queue '{queue}'", err=True)
    _run(DummyWorker(queue, delay=delay, concurrency=concurrency))


def run_dedup_worker(
    queue: str,
    *,
    batch_size: int = 256,
    mode: str = "dedup",
    threshold: float = 0.9,
    embedding: str = "lexical",
    model: Optional[str] = None,
    concurrency: Optional[int] = None,
) -> None:
    setup_logging(structured=True)
    from llmq_tpu.workers.dedup import DedupWorker

    click.echo(
        f"Starting dedup worker ({mode}, {embedding}) on queue '{queue}'",
        err=True,
    )
    _run(
        DedupWorker(
            queue,
            batch_size=batch_size,
            mode=mode,
            threshold=threshold,
            embedding=embedding,
            model=model,
            concurrency=concurrency,
        )
    )


def run_pipeline_worker(
    config_path: str, stage: str, *, concurrency: Optional[int] = None
) -> None:
    """Resolve a pipeline stage → its worker type, wired for stage routing
    (reference cli/worker.py:130-239)."""
    setup_logging(structured=True)
    pipeline = load_pipeline_config(config_path)
    stage_cfg = pipeline.get_stage_by_name(stage)
    if stage_cfg is None:
        click.echo(
            f"Stage '{stage}' not in pipeline '{pipeline.name}' "
            f"(stages: {[s.name for s in pipeline.stages]})",
            err=True,
        )
        sys.exit(1)
    queue = pipeline.get_stage_queue_name(stage)
    common = dict(pipeline=pipeline, stage_name=stage, concurrency=concurrency)
    if stage_cfg.worker in ("tpu", "vllm"):  # accept reference YAMLs naming vllm
        try:
            from llmq_tpu.workers.tpu_worker import TPUWorker
        except ImportError as exc:
            click.echo(f"TPU worker unavailable: {exc}", err=True)
            sys.exit(1)

        model = stage_cfg.config.get("model")
        if not model:
            click.echo(f"Stage '{stage}' needs config.model", err=True)
            sys.exit(1)
        worker = TPUWorker(
            queue,
            model=model,
            max_model_len=stage_cfg.config.get("max_model_len"),
            max_num_seqs=stage_cfg.config.get("max_num_seqs"),
            **common,
        )
    elif stage_cfg.worker == "dummy":
        from llmq_tpu.workers.dummy import DummyWorker

        worker = DummyWorker(
            queue, delay=float(stage_cfg.config.get("delay", 1.0)), **common
        )
    elif stage_cfg.worker in ("dedup", "semhash"):
        from llmq_tpu.workers.dedup import DedupWorker

        worker = DedupWorker(
            queue,
            batch_size=int(stage_cfg.config.get("batch_size", 256)),
            mode=stage_cfg.config.get("mode", "dedup"),
            threshold=float(stage_cfg.config.get("threshold", 0.9)),
            embedding=stage_cfg.config.get("embedding", "lexical"),
            model=stage_cfg.config.get("model"),
            **common,
        )
    else:
        click.echo(f"Unknown worker type '{stage_cfg.worker}'", err=True)
        sys.exit(1)
    click.echo(
        f"Starting {stage_cfg.worker} worker for stage '{stage}' of "
        f"pipeline '{pipeline.name}'",
        err=True,
    )
    _run(worker)


def _run(worker) -> None:
    try:
        asyncio.run(worker.run())
    except KeyboardInterrupt:
        pass
