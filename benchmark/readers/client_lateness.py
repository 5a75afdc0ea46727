"""How late the load generator ran: sent - due, over the measured requests."""
import numpy as np

import harness


def read(spec, record, result):
    client = record.get("client")
    if client is None or record.get("backlog"):
        return None
    plan, t_open = record["plan"], record["t_open"]
    late = [(client.sent[i] - (t_open + plan["due"][i])) * 1e3
            for i in record["measured"] if not np.isnan(client.sent[i])]
    return harness.quantile(late, float(spec["q"])) if late else None
