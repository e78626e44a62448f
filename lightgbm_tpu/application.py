"""Config-file driven CLI application.

Analog of the reference CLI (``src/main.cpp``, ``src/application/
application.cpp``): ``python -m lightgbm_tpu config=train.conf [k=v ...]``
with tasks train / predict / convert_model / refit (``config.h:29``).
Accepts the reference's ``key = value`` config-file grammar (comments with
``#``), so the reference's ``examples/*/train.conf`` files run unchanged.
"""
from __future__ import annotations

import os
import sys
from typing import Dict, List, Optional

import numpy as np

from .basic import Booster, Dataset
from .config import Config
from .engine import train as train_fn
from .utils.log import Log, LightGBMError


def parse_config_file(path: str) -> Dict[str, str]:
    """``key = value`` lines, ``#`` comments (reference ``Config::KV2Map`` /
    config-file loading, ``application.cpp:52-85``)."""
    out: Dict[str, str] = {}
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if not line or "=" not in line:
                continue
            k, v = line.split("=", 1)
            out[k.strip()] = v.strip()
    return out


def parse_argv(argv: List[str]) -> Dict[str, str]:
    """CLI ``key=value`` arguments; ``config=<file>`` pulls in a config file
    with CLI taking precedence (reference ``Application::Application``)."""
    cli: Dict[str, str] = {}
    for arg in argv:
        if "=" not in arg:
            raise LightGBMError(f"unknown argument {arg!r}; expected key=value")
        k, v = arg.split("=", 1)
        cli[k.strip()] = v.strip()
    params: Dict[str, str] = {}
    if "config" in cli:
        params.update(parse_config_file(cli.pop("config")))
    params.update(cli)                       # CLI overrides the file
    return params


class Application:
    """Task dispatcher (reference ``Application::Run``)."""

    def __init__(self, params: Dict[str, str]):
        self.raw_params = dict(params)
        self.config = Config.from_params(params)

    def run(self) -> None:
        task = self.config.task
        if task == "train":
            self.train()
        elif task == "predict":
            self.predict()
        elif task == "convert_model":
            self.convert_model()
        elif task == "refit":
            self.refit()
        else:
            raise LightGBMError(f"unknown task {task!r}")

    # ------------------------------------------------------------------
    def _resolve(self, path: str) -> str:
        """Paths in a config file are relative to the CWD, like the
        reference CLI."""
        return path

    def train(self) -> None:
        cfg = self.config
        if not cfg.data:
            raise LightGBMError("no training data: set data=<file>")
        params = dict(self.raw_params)
        params.pop("task", None)
        params.pop("data", None)
        params.pop("valid", None)
        for alias in ("valid_data", "valid_data_file", "test", "test_data",
                      "output_model", "input_model", "output_result"):
            params.pop(alias, None)
        train_set = Dataset(self._resolve(cfg.data), params=params)
        valid_sets, valid_names = [], []
        for i, v in enumerate(cfg.valid):
            valid_sets.append(Dataset(self._resolve(v), params=params,
                                      reference=train_set))
            valid_names.append(os.path.basename(v))
        init_model = cfg.input_model if cfg.input_model else None
        booster = train_fn(params, train_set,
                           num_boost_round=cfg.num_iterations,
                           valid_sets=valid_sets or None,
                           valid_names=valid_names or None,
                           init_model=init_model,
                           verbose_eval=cfg.metric_freq if cfg.verbosity >= 0 else False)
        booster.save_model(cfg.output_model)
        Log.info("Finished training; model saved to %s", cfg.output_model)

    def predict(self) -> None:
        cfg = self.config
        if not cfg.input_model:
            raise LightGBMError("no model: set input_model=<file>")
        if not cfg.data:
            raise LightGBMError("no data to predict: set data=<file>")
        booster = Booster(model_file=self._resolve(cfg.input_model))
        from .io.loader import load_file
        X = load_file(self._resolve(cfg.data), cfg)[0]
        pred = booster.predict(
            X, raw_score=cfg.predict_raw_score,
            pred_leaf=cfg.predict_leaf_index,
            pred_contrib=cfg.predict_contrib,
            num_iteration=cfg.num_iteration_predict,
            start_iteration=cfg.start_iteration_predict,
            predict_disable_shape_check=cfg.predict_disable_shape_check)
        pred = np.atleast_1d(pred)
        with open(cfg.output_result, "w") as f:
            if pred.ndim == 1:
                f.write("\n".join(repr(float(v)) for v in pred) + "\n")
            else:
                for row in pred:
                    f.write("\t".join(repr(float(v)) for v in row) + "\n")
        Log.info("Finished prediction; results saved to %s", cfg.output_result)

    def convert_model(self) -> None:
        cfg = self.config
        if not cfg.input_model:
            raise LightGBMError("no model: set input_model=<file>")
        booster = Booster(model_file=self._resolve(cfg.input_model))
        from .models.convert import model_to_cpp
        code = model_to_cpp(booster._gbdt)
        with open(cfg.convert_model, "w") as f:
            f.write(code)
        Log.info("Finished converting model; code saved to %s", cfg.convert_model)

    def refit(self) -> None:
        cfg = self.config
        if not cfg.input_model:
            raise LightGBMError("no model: set input_model=<file>")
        if not cfg.data:
            raise LightGBMError("no data: set data=<file>")
        booster = Booster(model_file=self._resolve(cfg.input_model))
        from .io.loader import load_file
        X, y = load_file(self._resolve(cfg.data), cfg)[:2]
        booster.refit(X, y, decay_rate=cfg.refit_decay_rate)
        booster.save_model(cfg.output_model)
        Log.info("Finished refit; model saved to %s", cfg.output_model)


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "obs-report":
        # observability subcommand: render the perf journal + telemetry
        # snapshot (docs/OBSERVABILITY.md) — not a key=value task
        from .obs.report import main as obs_report_main
        return obs_report_main(argv[1:])
    if not argv:
        print("usage: python -m lightgbm_tpu config=<file> [key=value ...]\n"
              "       python -m lightgbm_tpu obs-report [--format md|json] "
              "[--roofline] [--health [--health-url HOST:PORT]]")
        return 1
    try:
        Application(parse_argv(argv)).run()
    except LightGBMError as e:
        Log.warning("error: %s", e)
        return 2
    return 0
