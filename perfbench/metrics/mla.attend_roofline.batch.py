"""mla.attend_roofline.batch: least time of the counted latent attention
(absorbed, per phase: W_UK and W_UV on each query, scores and latent sum
over each live key, the latent rows read; the reference's
``counted_work``) over device time in scopes ``mla.attend`` and
``kv.gather``, in %."""
from bench import layer_work

CONFIG = "deepseek-v2-lite"


def read(ctx):
    t = layer_work.scope_seconds(ctx, "mla.attend", "kv.gather")
    if not t or not ctx["peak"] or layer_work.scope_seconds(ctx, "mla.attend") is None:
        return None
    work, res = layer_work.counted(CONFIG), ctx["res"]
    keys = layer_work.served_keys(ctx)
    phases = [work.latent_attention_work(*keys["prefill"], res["prefill_dispatches"]),
              work.latent_attention_work(*keys["decode"], res["decode_dispatches"])]
    return 100.0 * layer_work.least_seconds(phases, ctx["peak"]) / t
