"""Host-side layer metrics, from the program's own spans
(``mxnet_tpu/telemetry/tracing.py``), which run on the host's clock."""


def train_step_ms(ctx):
    """Mean duration of the program's ``train.step`` root spans in the
    window: what the host spends in ``DataParallelTrainer.step`` per step
    (prepare + h2d + dispatch + commit), hidden or not."""
    spans = [s for s in ctx.spans
             if s["name"] == "train.step" and s["parent"] is None
             and s["t0"] >= ctx.t_start and s["t1"] <= ctx.t_end]
    if not spans:
        return None
    return sum(s["t1"] - s["t0"] for s in spans) / len(spans) * 1e3
