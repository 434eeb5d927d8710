"""Lookup-table values evaluated by the per-value Rego walk per completed
audit cycle: the ``table_values_host`` args of the program's
``kind.host_prep`` spans of the window's sweeps (a parametric table
counts one per parameter and value).  None where the program's spans
carry no such arg."""


def read(rec):
    if rec.spans is None or rec.cycles <= 0:
        return None
    n = [e["args"]["table_values_host"] for e in rec.spans
         if e["name"] == "kind.host_prep"
         and "table_values_host" in e.get("args", {})]
    if not n:
        return None
    return float(sum(n)) / rec.cycles
