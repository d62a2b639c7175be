"""How late the load generator ran: 95th percentile, over the requests due
in the window, of the harness's release time minus the due time. A slot
that is not free (every session environment busy) counts in it too."""
from bench.harness import percentile


def compute(rec):
    late = [it.released - it.due for it in rec.due_in_window()
            if not it.refused]
    if not late:
        return None
    return 1e3 * percentile(late, 95)
