"""Train step: the compiled step's instructions that hold the optimizer's
update TOGETHER with another phase (a weight-gradient product with the
update as its epilogue reads ``bwd+update``): ``ops`` of every such set
of the census the program publishes as ``compile.fused_step.census.*``.
A device trace charges such an instruction to one scope only."""


def read(trace, counters, spans, cell):
    from mxnet_tpu import telemetry

    census = telemetry.snapshot().get("compile", {}).get(
        "fused_step", {}).get("census")
    if not census:
        return None
    return sum(row["ops"] for name, row in census.items()
               if "update" in name.split("+") and "+" in name)
