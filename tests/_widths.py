"""What the serve tests say about a scheduler's step widths.

The worker holds one compiled step a width (`Engine.serve_widths`), and
a step runs the narrowest that holds its longest row. A token is
bitwise a function of its request's history AND of the widths of the
steps that computed it. The `step_widths` fixture (conftest.py) runs a
test under both statements:

  "wide"      every scheduler of the test is held to the one
              `(slots, chunk)` step: one width sequence whatever the
              batch, so tokens AND logits are bitwise the same;
  "per-step"  the program's own choice: a request served alone decodes
              through the narrow step, beside a prefilling slot through
              the wide one. On the tests' float32 sizes the tokens are
              still equal, and the logits agree to ACROSS_WIDTHS_ATOL.
"""

import numpy as np

# two correct float32 formulations of the tiny models (logits up to
# 0.75): what the statement allows. XLA's CPU backend reads 0.0 at
# these sizes; the chip's bf16 reading is in docs/serving.md
ACROSS_WIDTHS_ATOL = 1e-4


def hold_to_the_wide_step(monkeypatch):
    from triton_dist_tpu.models import Engine

    monkeypatch.setattr(Engine, "serve_widths",
                        lambda self, chunk: (chunk,))


def record_logits(sch):
    """Wrap every compiled step of `sch.worker` so that each call keeps
    its `last_logits`; returns `logits_of`, which after the run gives
    {request_id: (emitted tokens, vocab) float32 array} — the row a
    step returned for each token a request emitted, in order (a
    host-loop scheduler without spec: one emission a plan entry whose
    row ends the prompt or decodes; no eviction). `prompt_lens`:
    {request_id: prompt length}."""
    w = sch.worker
    seen = []

    def recording(fn):
        def call(*a):
            out = fn(*a)
            seen.append(np.asarray(out[1]))
            return out
        return call

    w._fn = recording(w._fn)
    for width in w._narrow:
        w._narrow[width] = recording(w._narrow[width])

    def logits_of(prompt_lens):
        pos, out = {}, {}
        steps = [h for h in sch.history if h.get("kind") == "step"]
        assert len(steps) == len(seen)
        for h, last in zip(steps, seen):
            for slot, (rid, state, n) in h["slots"].items():
                if state == "prefill":
                    pos[rid] = pos.get(rid, 0) + n
                    if pos[rid] < prompt_lens[rid]:
                        continue  # a chunk inside the prompt
                out.setdefault(rid, []).append(last[slot])
        return {rid: np.stack(rows) for rid, rows in out.items()}

    return logits_of
