"""triton_dist_tpu.serve — continuous-batching serving plane.

The scheduler/worker split of the inference Engine (ROADMAP item 1; the
production shape of the reference's Engine.serve + socket model_server,
ref: mega_triton_kernel/test/models/model_server.py): requests queue
with priorities, a Scheduler assembles a heterogeneous batch each step
— new requests' prefill chunks beside in-flight decode steps — and a
Worker replays a jit'd step function (engine.make_serve_step, one
compiled step a width: a decode-only step beside the mixed one) over a
shared paged-KV pool with admission, eviction + requeue, and streaming
detokenized output.

Quick start (docs/serving.md has the full story):

    from triton_dist_tpu.serve import Scheduler

    sch = Scheduler(engine, slots=4, page=64)
    req = sch.submit(prompt_ids, max_new_tokens=32, stream=True)
    sch.start()                      # background serving thread
    for tok, piece in req.stream:    # streams as the batch runs
        ...
    sch.stop()

Because the serve step's geometry is fixed and XLA row numerics are
independent of batch composition, every request's tokens are
bit-identical (temperature 0 — and, via per-(seed, index) keys, sampled
too) to a sequential `Engine.serve(..., slots=, chunk=)` run of the
same geometry, including across an eviction/requeue
(tests/test_serve.py pins this).
"""

from triton_dist_tpu.serve.kv_pool import (  # noqa: F401
    KVPool,
    PoolExhausted,
    pages_for,
)
from triton_dist_tpu.serve.prefix import PrefixCache  # noqa: F401
from triton_dist_tpu.serve.queue import QueueFull, RequestQueue  # noqa: F401
from triton_dist_tpu.serve.request import (  # noqa: F401
    Detokenizer,
    Request,
    RequestState,
    TokenStream,
    summarize,
)
from triton_dist_tpu.serve.scheduler import Scheduler  # noqa: F401
from triton_dist_tpu.serve.worker import Worker  # noqa: F401
