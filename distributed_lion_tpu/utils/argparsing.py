"""Dataclass-driven CLI parsing — the HfArgumentParser role.

The reference parses CLI flags into dataclass groups via HfArgumentParser,
including JSON-file configs (/root/reference/run_clm.py:252-258,
sft_llama2.py:42-43). Same surface here: every dataclass field becomes a
``--flag``; booleans accept ``--flag`` / ``--flag false``; a single JSON-file
argument populates all groups.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import typing
from typing import Optional, Sequence, Type


def _str2bool(v: str) -> bool:
    if isinstance(v, bool):
        return v
    if v.lower() in ("yes", "true", "t", "1"):
        return True
    if v.lower() in ("no", "false", "f", "0"):
        return False
    raise argparse.ArgumentTypeError(f"boolean value expected, got {v!r}")


def _unwrap_optional(tp):
    origin = typing.get_origin(tp)
    if origin is typing.Union:
        args = [a for a in typing.get_args(tp) if a is not type(None)]
        if len(args) == 1:
            return args[0], True
    return tp, False


def build_parser(dataclass_types: Sequence[Type]) -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        description="distributed_lion_tpu", allow_abbrev=False,
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    seen = set()
    for dc in dataclass_types:
        group = parser.add_argument_group(dc.__name__)
        for f in dataclasses.fields(dc):
            if not f.metadata.get("cli", True):
                # programmatic-only field (metadata {'cli': False}): no
                # flag, and it may shadow a same-named flag owned by
                # another group (e.g. TrainConfig.remat_policy vs
                # run_clm ModelArguments.remat_policy — the CLI flag
                # drives the model config; the TrainConfig field is the
                # Trainer-builder override bench/tests use)
                continue
            if f.name in seen:
                raise ValueError(f"duplicate field {f.name!r} across dataclasses")
            seen.add(f.name)
            tp, _ = _unwrap_optional(f.type if not isinstance(f.type, str) else eval(f.type, vars(typing) | {"Optional": Optional}))
            default = f.default if f.default is not dataclasses.MISSING else (
                f.default_factory() if f.default_factory is not dataclasses.MISSING else None
            )
            kw: dict = {"default": default, "help": f.metadata.get("help", "")}
            if tp is bool:
                # --flag (→ true) or --flag false, like HfArgumentParser
                kw.update(type=_str2bool, nargs="?", const=True)
            elif typing.get_origin(tp) in (list, typing.List):
                kw.update(type=typing.get_args(tp)[0] if typing.get_args(tp) else str, nargs="*")
            elif tp in (int, float, str):
                kw.update(type=tp)
            else:
                kw.update(type=str)
            group.add_argument(f"--{f.name}", **kw)
    return parser


def parse_dataclasses(
    dataclass_types: Sequence[Type], args: Optional[Sequence[str]] = None
) -> tuple:
    """Parse argv (or a JSON config file given as the sole argument) into one
    instance per dataclass, in order."""
    argv = list(sys.argv[1:] if args is None else args)
    if len(argv) == 1 and argv[0].endswith(".json"):
        values = json.loads(pathlib.Path(argv[0]).read_text())
    else:
        parser = build_parser(dataclass_types)
        ns = parser.parse_args(argv)
        values = vars(ns)

    out = []
    for dc in dataclass_types:
        # cli:False fields never populate from parsed flags or JSON —
        # without this, a same-named FLAG owned by another group leaks in
        # (e.g. ModelArguments.remat_policy default 'auto' would land in
        # TrainConfig.remat_policy and break `--remat false`)
        kwargs = {f.name: values[f.name] for f in dataclasses.fields(dc)
                  if f.name in values and f.metadata.get("cli", True)}
        out.append(dc(**kwargs))
    return tuple(out)
