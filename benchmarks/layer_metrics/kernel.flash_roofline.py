"""Roofline share of the Pallas flash-attention kernels in a train step:
the least time the chip could take for the causal forward and backward of
every layer of every traced step (operations and bytes from shapes,
``harness/flops.py``; the larger of operations over peak FLOP/s and bytes
over peak bandwidth), over the summed device time of the
``_flash_fwd_impl`` and ``_flash_bwd_impl`` events of the traced slice.
Absent (None) where no such kernel ran: a sequence under the flash gate
keeps attention on XLA's path."""
META = {"name": "kernel.flash_roofline", "unit": "%",
        "layer": "attention kernels", "moves": "train_tokens_per_s",
        "regimes": ["train"]}


def read(ctx):
    tr, fl = ctx["trace_reduce"], ctx["flops"]
    n_fwd, t_fwd = tr.op_seconds(ctx["trace"], "_flash_fwd_impl")
    n_bwd, t_bwd = tr.op_seconds(ctx["trace"], "_flash_bwd_impl")
    if n_fwd == 0 and n_bwd == 0:
        return None
    cfg = ctx["config"]["config"]
    heads = cfg["num_heads"]
    shape = (ctx["measured"]["batch"] // ctx["cell"]["chips"], heads,
             ctx["measured"]["seq"], cfg["hidden_size"] // heads)
    steps = len(tr.module_durations_s(ctx["trace"], "jit__step")) \
        // ctx["cell"]["chips"]
    calls = steps * cfg["num_layers"]
    least, roofs = 0.0, []
    for flops, nbytes in ((fl.flash_fwd_flops(*shape), fl.flash_fwd_bytes(*shape)),
                          (fl.flash_bwd_flops(*shape), fl.flash_bwd_bytes(*shape))):
        t, roof = fl.roofline_seconds(flops, nbytes, ctx["peaks"])
        least += calls * t
        roofs.append(roof)
    ctx["log"](f"flash kernels: {n_fwd} forward events {t_fwd * 1e3:.2f} ms, "
               f"{n_bwd} backward events {t_bwd * 1e3:.2f} ms over {steps} "
               f"steps x {cfg['num_layers']} layers at {shape}; least "
               f"{least * 1e3:.2f} ms; bound by {roofs[0]} (forward), "
               f"{roofs[1]} (backward)")
    return 100.0 * least / (t_fwd + t_bwd)
