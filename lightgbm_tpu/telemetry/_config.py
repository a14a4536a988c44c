"""Telemetry master switch (kept in its own leaf module so metrics/
trace/train_record can read it without import cycles).

Default ON — accumulation is cheap host-side bookkeeping and purely
observational (bit-identical training is a tested contract).  Disable
with ``LGBM_TPU_TELEMETRY=0`` or ``lightgbm_tpu.telemetry.disable()``;
the span tracer's Python-side event list stays separately opt-in."""

from __future__ import annotations

import os

_enabled = os.environ.get("LGBM_TPU_TELEMETRY", "1") != "0"


def enabled() -> bool:
    return _enabled


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False
