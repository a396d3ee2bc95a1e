"""Environment-flag parsing for the kernel layer — deliberately jax-free
so config validation (e.g. at backend construction) never pays the
pallas import for ten lines of os.environ parsing."""

from __future__ import annotations

import os


def analytics_max_rows(default: int = 256) -> int:
    """Per-request weight-row cap for the Prism analytics routes (MatVec
    rows / GroupBySum groups): DDS_ANALYTICS_MAX_ROWS when set, else
    `default` (the `[analytics] max-rows` config value flows in here).
    Whatever wins is validated the same loud way DDS_PROD_TB is — int,
    within [1, 65536] — so a typo fails at server construction with an
    actionable message instead of surfacing as a per-request 500. The
    ceiling bounds the weight-matrix kernel work one request can demand:
    rows x columns x exponent-width modmuls all scale with it."""
    env = os.environ.get("DDS_ANALYTICS_MAX_ROWS", "").strip()
    source = "DDS_ANALYTICS_MAX_ROWS" if env else "[analytics] max-rows"
    raw = env if env else default
    try:
        rows = int(raw)
    except (TypeError, ValueError):
        raise ValueError(
            f"{source} must be an integer row count, got {raw!r}"
        ) from None
    if not 1 <= rows <= 65536:
        raise ValueError(
            f"{source} must be in [1, 65536] (per-request analytics row "
            f"cap), got {rows}"
        )
    return rows


def secret_device(default: bool = False) -> bool:
    """Sanctum device opt-in: run the secret-material CRT decrypt legs
    as a fused batched device dispatch instead of the host-only default
    (DEPLOY.md "Secret-material trust boundary (Sanctum)").
    DDS_SECRET_DEVICE when set, else `default` (the `[crypto]
    secret-device` config value flows in here). Validated the same loud
    way DDS_PROD_TB is — a typo fails at provider construction with an
    actionable message, because an operator who believes they opted
    IN (or OUT) of device residency for key material must never be
    silently wrong about it."""
    env = os.environ.get("DDS_SECRET_DEVICE", "").strip().lower()
    if not env:
        if not isinstance(default, bool):
            raise ValueError(
                "[crypto] secret-device must be a boolean, got "
                f"{default!r}"
            )
        return default
    if env in ("1", "true", "on", "yes"):
        return True
    if env in ("0", "false", "off", "no"):
        return False
    raise ValueError(
        f"unknown DDS_SECRET_DEVICE value {env!r} (use 1/true/on/yes or "
        "0/false/off/no)"
    )


def prod_tb() -> int | None:
    """DDS_PROD_TB: lane-tile override for the MXU product kernel, or None
    when unset. Validated HERE — int, positive, multiple of the 128-lane
    width — so a typo fails loudly at flag-read time with an actionable
    message instead of an opaque ValueError (or a mis-shaped kernel) deep
    inside a trace."""
    env = os.environ.get("DDS_PROD_TB", "").strip()
    if not env:
        return None
    try:
        tb = int(env)
    except ValueError:
        raise ValueError(
            f"DDS_PROD_TB must be an integer number of lanes, got {env!r}"
        ) from None
    if tb <= 0 or tb % 128:
        raise ValueError(
            f"DDS_PROD_TB must be a positive multiple of 128 (the TPU lane "
            f"width), got {tb}"
        )
    return tb
