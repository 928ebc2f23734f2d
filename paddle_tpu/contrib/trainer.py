"""High-level Trainer API
(reference: python/paddle/fluid/contrib/trainer.py — the event-driven
Trainer the book examples used: program built by callbacks, epoch/step
events, checkpointing via CheckpointConfig, test()/save_params()/
save_inference_model()).

TPU-native simplifications: the executor path is the block-compiling
Executor; distributed setup maps PADDLE_TRAINING_ROLE env to the
DistributeTranspiler exactly like the reference; checkpoints are
serial-numbered directories with a success marker and bounded retention.
"""

from __future__ import annotations

import os
import shutil
from typing import Callable, List, Optional, Sequence

from .. import io as fluid_io
from ..core.executor import Executor
from ..core.framework import Program, program_guard, unique_name_guard
from ..core.place import CPUPlace, TPUPlace
from ..core.scope import Scope, global_scope, scope_guard
from ..data_feeder import DataFeeder

__all__ = [
    "BeginEpochEvent", "EndEpochEvent", "BeginStepEvent", "EndStepEvent",
    "CheckpointConfig", "Trainer",
]


class BeginEpochEvent:
    def __init__(self, epoch_id: int):
        self.epoch = epoch_id


class EndEpochEvent:
    def __init__(self, epoch_id: int):
        self.epoch = epoch_id


class BeginStepEvent:
    def __init__(self, epoch_id: int, step_id: int):
        self.epoch = epoch_id
        self.step = step_id
        # mirrors the reference flag: handlers set this to fetch metrics
        self.fetch_metrics = True


class EndStepEvent:
    def __init__(self, epoch_id: int, step_id: int, metrics):
        self.epoch = epoch_id
        self.step = step_id
        self.metrics = metrics


class CheckpointConfig:
    """reference: trainer.py:100 — serial-numbered checkpoint dirs with
    bounded retention and an epoch/step save cadence."""

    def __init__(self, checkpoint_dir: Optional[str] = None,
                 max_num_checkpoints: int = 3,
                 epoch_interval: int = 1, step_interval: int = 10):
        self.checkpoint_dir = checkpoint_dir or os.path.join(
            os.getcwd(), "checkpoint")
        self.max_num_checkpoints = max_num_checkpoints
        self.epoch_interval = max(1, epoch_interval)
        self.step_interval = max(1, step_interval)
        self.epoch_id = 0
        self.step_id = 0
        self.load_serial: Optional[int] = None


def check_and_get_place(place):
    """reference: trainer.py:143 — default to the TPU where the backend
    has one, else the CPU."""
    if place is not None:
        return place
    import jax

    return CPUPlace() if jax.default_backend() == "cpu" else TPUPlace()


class Trainer:
    """Event-driven training harness (reference: trainer.py:169).

    Args:
        train_func: callback building the program; returns [loss, ...]
            fetch vars (run under this trainer's program guard).
        optimizer_func: returns the Optimizer to apply.
        place, param_path (warm start), checkpoint_config, parallel.
    """

    def __init__(self, train_func: Callable, optimizer_func: Callable,
                 param_path: Optional[str] = None, place=None,
                 parallel: bool = False,
                 checkpoint_config: Optional[CheckpointConfig] = None):
        self.__stop = False
        self.parallel = parallel
        self.checkpoint_cfg = checkpoint_config
        self.place = check_and_get_place(place)
        self.scope = Scope()
        self.startup_program = Program()
        self.train_program = Program()

        # fresh name counters: the Inferencer rebuilds the graph under its
        # own guard, so auto-generated param names line up for checkpoints
        with program_guard(self.train_program, self.startup_program), \
                unique_name_guard():
            outs = train_func()
            if isinstance(outs, (list, tuple)):
                self.train_func_outputs = list(outs)
            else:
                self.train_func_outputs = [outs]
            self.loss = self.train_func_outputs[0]
            optimizer = optimizer_func()
            optimizer.minimize(self.loss)

        self.trainer_id = int(os.getenv("PADDLE_TRAINER_ID", "0"))
        self._dist_transpile_if_necessary()

        with self._prog_and_scope_guard():
            exe = Executor(self.place)
            exe.run(self.startup_program)
            if param_path:
                fluid_io.load_persistables(
                    exe, param_path, main_program=self.train_program)
            if self.checkpoint_cfg:
                self._load_checkpoint()

    # -- distributed setup (reference: _dist_transpile_if_necessary) ----
    def _dist_transpile_if_necessary(self):
        role = os.getenv("PADDLE_TRAINING_ROLE")
        if role is None:
            return
        from ..transpiler import DistributeTranspiler

        port = os.getenv("PADDLE_PSERVER_PORT", "6174")
        ips = os.getenv("PADDLE_PSERVER_IPS", "")
        eplist = [f"{ip.strip()}:{port}" for ip in ips.split(",") if ip]
        pserver_endpoints = ",".join(eplist)
        trainers = int(os.getenv("PADDLE_TRAINERS", "1"))
        current_endpoint = (
            os.getenv("PADDLE_CURRENT_IP", "") + ":" + port)
        t = DistributeTranspiler()
        with program_guard(self.train_program, self.startup_program):
            t.transpile(self.trainer_id, pservers=pserver_endpoints,
                        trainers=trainers)
        if role == "PSERVER":
            self.train_program = t.get_pserver_program(current_endpoint)
            self.startup_program = t.get_startup_program(
                current_endpoint, self.train_program)
        elif role == "TRAINER":
            self.train_program = t.get_trainer_program()
        else:
            raise ValueError(
                "PADDLE_TRAINING_ROLE must be PSERVER or TRAINER"
            )

    def _prog_and_scope_guard(self):
        import contextlib

        @contextlib.contextmanager
        def guard():
            with program_guard(self.train_program, self.startup_program):
                with scope_guard(self.scope):
                    yield

        return guard()

    def stop(self):
        """Handlers call this to end training early."""
        self.__stop = True

    # -- training / testing --------------------------------------------
    def train(self, num_epochs: int, event_handler: Callable,
              reader=None, feed_order: Optional[Sequence[str]] = None):
        """reference: trainer.py train — executor loop with events."""
        with self._prog_and_scope_guard():
            exe = Executor(self.place)
            feeder = self._feeder(feed_order)
            start_epoch = (self.checkpoint_cfg.epoch_id
                           if self.checkpoint_cfg else 0)
            for epoch_id in range(start_epoch, num_epochs):
                event_handler(BeginEpochEvent(epoch_id))
                for step_id, data in enumerate(reader()):
                    if self.__stop:
                        return
                    begin = BeginStepEvent(epoch_id, step_id)
                    event_handler(begin)
                    fetch = (self.train_func_outputs
                             if begin.fetch_metrics else [])
                    metrics = exe.run(
                        program=self.train_program,
                        feed=feeder.feed(data), fetch_list=fetch,
                    )
                    event_handler(EndStepEvent(epoch_id, step_id, metrics))
                    if (self.checkpoint_cfg
                            and step_id % self.checkpoint_cfg.step_interval
                            == 0):
                        self._save_checkpoint(epoch_id, step_id)
                event_handler(EndEpochEvent(epoch_id))
                if (self.checkpoint_cfg
                        and epoch_id % self.checkpoint_cfg.epoch_interval
                        == 0):
                    self._save_checkpoint(epoch_id, 0)

    def test(self, reader, feed_order: Optional[Sequence[str]] = None
             ) -> List[float]:
        """Mean of the train_func outputs over the reader
        (reference: trainer.py _test_by_executor)."""
        import numpy as np

        with self._prog_and_scope_guard():
            exe = Executor(self.place, donate_states=False)
            feeder = self._feeder(feed_order)
            test_prog = self.train_program.clone(for_test=True)
            accumulated = [0.0] * len(self.train_func_outputs)
            count = 0
            for data in reader():
                outs = exe.run(program=test_prog, feed=feeder.feed(data),
                               fetch_list=self.train_func_outputs)
                for i, v in enumerate(outs):
                    accumulated[i] += float(np.ravel(np.asarray(v))[0])
                count += 1
            return [a / max(1, count) for a in accumulated]

    def _feeder(self, feed_order):
        if feed_order is None:
            raise ValueError("feed_order is required (list of data names)")
        feed_list = [
            self.train_program.global_block().var(n) for n in feed_order
        ]
        return DataFeeder(feed_list=feed_list, place=self.place)

    # -- persistence ----------------------------------------------------
    def save_params(self, param_path: str):
        with self._prog_and_scope_guard():
            exe = Executor(self.place)
            fluid_io.save_persistables(exe, param_path,
                                       main_program=self.train_program)

    def save_inference_model(self, param_path: str,
                             feeded_var_names: Sequence[str],
                             target_var_indexes: Sequence[int]):
        with self._prog_and_scope_guard():
            exe = Executor(self.place)
            fluid_io.save_inference_model(
                param_path, list(feeded_var_names),
                [self.train_func_outputs[i] for i in target_var_indexes],
                exe, main_program=self.train_program,
            )

    def _serial_dir(self, serial: int) -> str:
        return os.path.join(self.checkpoint_cfg.checkpoint_dir, str(serial))

    def _save_checkpoint(self, epoch_id: int, step_id: int):
        cfg = self.checkpoint_cfg
        os.makedirs(cfg.checkpoint_dir, exist_ok=True)
        serial = self._latest_serial() + 1
        d = self._serial_dir(serial)
        exe = Executor(self.place)
        fluid_io.save_persistables(exe, d, main_program=self.train_program)
        with open(os.path.join(d, "trainer_args.json"), "w") as f:
            import json

            json.dump({"epoch_id": epoch_id, "step_id": step_id}, f)
        with open(os.path.join(d, "_SUCCESS"), "w"):
            pass
        self._scroll_delete()

    def _latest_serial(self) -> int:
        cfg = self.checkpoint_cfg
        best = -1
        if os.path.isdir(cfg.checkpoint_dir):
            for name in os.listdir(cfg.checkpoint_dir):
                if name.isdigit() and os.path.exists(
                        os.path.join(cfg.checkpoint_dir, name, "_SUCCESS")):
                    best = max(best, int(name))
        return best

    def _scroll_delete(self):
        cfg = self.checkpoint_cfg
        serials = sorted(
            int(n) for n in os.listdir(cfg.checkpoint_dir) if n.isdigit()
        )
        for s in serials[:-cfg.max_num_checkpoints]:
            shutil.rmtree(self._serial_dir(s), ignore_errors=True)

    def _load_checkpoint(self):
        import json

        serial = self._latest_serial()
        if serial < 0:
            return
        d = self._serial_dir(serial)
        exe = Executor(self.place)
        fluid_io.load_persistables(exe, d, main_program=self.train_program)
        args_path = os.path.join(d, "trainer_args.json")
        if os.path.exists(args_path):
            with open(args_path) as f:
                args = json.load(f)
            self.checkpoint_cfg.epoch_id = int(args["epoch_id"])
            self.checkpoint_cfg.step_id = int(args["step_id"])


