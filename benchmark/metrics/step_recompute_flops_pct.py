"""Train step: the recomputed forward's share of the compiled step's
matrix work, %: FLOPs of the convolutions and dots whose own ``op_name``
stands under what ``jax.checkpoint`` runs again
(``compile.fused_step.matrix_flops.recompute``, under
``MXNET_BACKWARD_DO_MIRROR``) over all phases'. Product by product, so a
fusion that holds a recomputed product beside a backward one is split. 0
where nothing is recomputed; a Pallas kernel's work is in neither (a
custom call states no FLOPs)."""


def read(trace, counters, spans, cell):
    from mxnet_tpu import telemetry

    by_phase = telemetry.snapshot().get("compile", {}).get(
        "fused_step", {}).get("matrix_flops")
    total = sum((by_phase or {}).values())
    if not total:
        return None
    return 100.0 * by_phase.get("recompute", 0.0) / total
