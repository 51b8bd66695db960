"""HTTP layer (``launch/serve_miner.py``): seconds per answer that the
client waits beyond the service's own ``latency_s`` — itemset decode, JSON
encoding, transfer and the client's parse."""


def read(run):
    if not run.answers:
        return None
    return sum(a.client_s - a.latency_s for a in run.answers) / len(run.answers)
