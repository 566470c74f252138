"""Tokens each held expert computes per decode execution, as a mean over the
window: the program's counter ``moe_pairs_here`` (token-expert pairs routed
to the experts this chip holds, summed over expert layers and decode
executions) over ``moe_expert_slots`` (held experts x expert layers x decode
executions).  It says how near the cell comes to the expert batch of the
deployment it stands for.  ``None`` where the program raised no such
counters (a model without experts, or a program that does not count)."""


def read(rec):
    here = rec.program_counters.get("moe_pairs_here")
    slots = rec.program_counters.get("moe_expert_slots")
    return here / slots if here is not None and slots else None
