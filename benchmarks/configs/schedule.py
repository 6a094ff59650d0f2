"""What the configuration families share about the optimizer's schedule."""

from __future__ import annotations

from typing import Any, Callable, Dict, List

from benchmarks.reference import perceiver as ref


def cli_flags(cfg: Dict[str, Any]) -> List[str]:
    """The train CLIs' flags for the schedule the configuration states."""
    if not cfg.get("one_cycle_lr"):
        return []
    return ["--one_cycle_lr", "--max_steps", str(cfg["max_steps"]),
            "--one_cycle_pct_start", str(cfg["one_cycle_pct_start"])]


def learning_rate(cfg: Dict[str, Any]) -> Callable[[int], float]:
    """``step -> rate`` for the plain reference."""
    if cfg.get("one_cycle_lr"):
        return lambda step: ref.one_cycle_lr(step, cfg["max_steps"], cfg["learning_rate"],
                                             cfg["one_cycle_pct_start"])
    return lambda step: cfg["learning_rate"]
