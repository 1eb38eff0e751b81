"""Serving launcher: the engine on a synthetic context-sharing workload.

By default the compute is the arch's reduced (CPU-sized) config while the
economics model the full arch (``EngineConfig.cost_arch``).  ``--no-reduced``
serves the published config at its full width in its own dtype, with random
params from ``--seed`` initialised under jit on the first device — the
path ``chip_smoke.py`` drives on a TPU.

    PYTHONPATH=src python -m repro.launch.serve --arch llama-7b \\
        --requests 32 --contexts 8 --policy cost --compress
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --no-reduced \\
        --requests 16 --contexts 4 --context-len 1024 --prompt-len 64 \\
        --output-len 16 --slots 8 --policy always

The module's functions are the launcher's body: ``parse_args`` →
``serve`` (``setup`` + ``init_params`` + ``workload``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax

from repro.configs import get_config, list_configs, reduced_config
from repro.configs.base import ArchConfig
from repro.core.perf_model import PerfModel, V100_X4_HF, tpu_v5e
from repro.core.pricing import AWS_PAPER, Pricing, tpu_v5e_pod
from repro.data.synthetic import WorkloadSpec, serving_workload
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry
from repro.serving import (
    AlwaysReusePlanner,
    CostAwarePlanner,
    EngineConfig,
    ServingEngine,
)
from repro.serving.metrics import ServingSummary
from repro.serving.request import Request
from repro.serving.scheduler import HedgePolicy


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="serving launcher")
    ap.add_argument("--arch", default="llama-7b", choices=list_configs())
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--contexts", type=int, default=8)
    ap.add_argument("--context-len", type=int, default=96)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--output-len", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--policy", default="cost", choices=["cost", "always", "never"])
    ap.add_argument("--compress", action="store_true", help="int8 storage tier")
    ap.add_argument("--overlap", action="store_true", help="prefetch overlap")
    ap.add_argument("--hedge", action="store_true", help="hedged storage reads")
    ap.add_argument("--platform", default="paper", choices=["paper", "tpu"])
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction, default=True,
                    help="reduced compute with full-size economics (CPU); "
                    "--no-reduced serves the published config")
    ap.add_argument("--seed", type=int, default=0, help="params + workload seed")
    ap.add_argument("--json", action="store_true")
    return ap.parse_args(argv)


@dataclasses.dataclass(frozen=True)
class Setup:
    """Everything an engine (or every replica of a cluster) is built from."""

    cfg: ArchConfig
    engine_cfg: EngineConfig
    planner_factory: Callable[[], Any]
    pricing: Pricing
    perf: PerfModel


def setup(args: argparse.Namespace, **engine_overrides) -> Setup:
    """Resolve the served config and engine settings from the arguments;
    ``engine_overrides`` replace EngineConfig fields (e.g. paged_decode)."""
    full_cfg = get_config(args.arch)
    cfg = reduced_config(full_cfg) if args.reduced else full_cfg
    if args.platform == "tpu":
        pricing, perf = tpu_v5e_pod(256), PerfModel(tpu_v5e(256))
    else:
        pricing, perf = AWS_PAPER, PerfModel(V100_X4_HF)
    # room for the request, rounded up to whole 128-token kv blocks: the
    # paged pool asserts it, and the TPU decode kernel takes only caches the
    # block tiles (KernelUnsupported otherwise)
    need = args.context_len + args.prompt_len + args.output_len + 32
    ec = EngineConfig(
        max_slots=args.slots,
        max_len=-(-need // 128) * 128,
        chunk_tokens=16,
        reuse_enabled=args.policy != "never",
        compress_tier="io2" if args.compress else None,
        overlap_load=args.overlap,
        hedge=HedgePolicy() if args.hedge else None,
        cost_arch=args.arch if args.reduced else None,
    )
    ec = dataclasses.replace(ec, **engine_overrides)
    planner = AlwaysReusePlanner if args.policy == "always" else CostAwarePlanner
    return Setup(cfg, ec, planner, pricing, perf)


def init_params(cfg: ArchConfig, seed: int = 0):
    """Random params from ``seed``, initialised under jit directly on the
    first device."""
    api = registry.get_model(cfg)
    init = jax.jit(
        lambda key: api.init(key, cfg),
        out_shardings=jax.sharding.SingleDeviceSharding(jax.devices()[0]),
    )
    return init(jax.random.PRNGKey(seed))


def workload(cfg: ArchConfig, args: argparse.Namespace) -> List[Request]:
    """``--contexts`` contexts, each asked ``--requests // --contexts``
    times, with Poisson arrivals — seeded by ``--seed``."""
    spec = WorkloadSpec(
        n_contexts=args.contexts,
        reuses_per_context=max(1, args.requests // args.contexts),
        context_len=args.context_len,
        prompt_len=args.prompt_len,
        output_len=args.output_len,
        arrival_rate_per_s=2.0,
        seed=args.seed,
    )
    return serving_workload(cfg, spec)


def serve(
    args: argparse.Namespace, *, params=None, **engine_overrides
) -> Tuple[ServingEngine, ServingSummary]:
    """Build the engine on the first device (params from ``--seed`` unless
    given), serve the workload to completion, and return the engine and its
    summary."""
    s = setup(args, **engine_overrides)
    if params is None:
        params = init_params(s.cfg, args.seed)
    engine = ServingEngine(
        s.cfg, params, engine_cfg=s.engine_cfg, planner=s.planner_factory(),
        pricing=s.pricing, perf=s.perf, device=jax.devices()[0],
    )
    for req in workload(s.cfg, args):
        engine.submit(req)
    return engine, engine.run()


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = parse_args(argv)
    enable_compile_cache()
    engine, summary = serve(args)
    if args.json:
        print(json.dumps({**summary.as_dict(), "store": engine.store.stats()}, indent=2))
    else:
        print(f"served {summary.n_requests} requests "
              f"({summary.reuse_hits} reuse hits) on {engine.cfg.name}")
        print(f"  cost ${summary.total_cost:.4f} "
              f"(compute {summary.compute_cost:.4f} / storage {summary.storage_cost:.6f} "
              f"/ transfer {summary.transfer_cost:.6f})")
        print(f"  TTFT mean {summary.mean_ttft_s:.3f}s p99 {summary.p99_ttft_s:.3f}s; "
              f"e2e p99 {summary.p99_e2e_s:.3f}s (modeled)")


if __name__ == "__main__":
    main()
