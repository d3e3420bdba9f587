"""paddle_tpu — a TPU-native deep-learning framework with the capabilities of
PaddlePaddle Fluid (reference mounted at /root/reference; see SURVEY.md).

Public surface mirrors `paddle.fluid`: program-construction layers API,
append_backward autodiff, optimizers, Executor/ParallelExecutor, readers,
metrics, io — implemented TPU-first: programs trace to jax functions compiled
by XLA; parallelism is SPMD over a jax.sharding.Mesh with compiled collectives.
"""

import time as _time

_import_began = _time.perf_counter()    # the `paddle_tpu/import` span's start

from .core import compile_cache as _compile_cache  # noqa: E402

_compile_cache.configure()

from . import clip, initializer, layers, optimizer, regularizer  # noqa: F401,E402
from .core import (CPUPlace, Place, TPUPlace, default_place,  # noqa: F401
                   device_count, devices, is_compiled_with_tpu)
from .core import flags  # noqa: F401
from .core import unique_name  # noqa: F401
from .framework.backward import append_backward, calc_gradient  # noqa: F401
from .framework.executor import Executor  # noqa: F401
from .framework.program import (Program, Variable, default_main_program,  # noqa: F401
                                default_startup_program, program_guard,
                                reset_default_programs)
from .framework.registry import registered_ops  # noqa: F401
from .framework.scope import Scope, global_scope, reset_global_scope  # noqa: F401
from .framework.selected_rows import SelectedRows  # noqa: F401
from .framework.passes import (Analyzer, Pass, get_pass,  # noqa: F401
                               register_pass, registered_passes)
from .framework.analysis import (analyze_program, check_program,  # noqa: F401
                                 infer_program, op_loc, verify_program)
from .param_attr import ParamAttr  # noqa: F401
from . import nets  # noqa: F401,E402
from . import models  # noqa: F401,E402
from . import io  # noqa: F401,E402
from . import sharded_checkpoint  # noqa: F401,E402
from .inferencer import Inferencer, Predictor  # noqa: F401,E402
from . import serving  # noqa: F401,E402
from . import serving_engine  # noqa: F401,E402
from . import metrics  # noqa: F401,E402
from . import observability  # noqa: F401,E402
from . import profiler  # noqa: F401,E402
from . import debugger  # noqa: F401,E402
from .trainer import (BeginEpochEvent, BeginStepEvent,  # noqa: F401,E402
                      CheckpointConfig, EndEpochEvent, EndStepEvent, Trainer,
                      load_checkpoint, save_checkpoint)
from .io import (load_inference_model, load_params,  # noqa: F401,E402
                 load_persistables, load_vars, save_inference_model,
                 save_params, save_persistables, save_vars)

__version__ = "0.1.0"

# what a process pays before its first statement of work: a `compile` span
# like the other once-only costs (JAX's own import is in it where nothing
# imported JAX before)
observability.tracing.record_span("compile", "paddle_tpu/import",
                                  _import_began, _time.perf_counter())
