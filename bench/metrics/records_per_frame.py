"""Records per wire frame over the window: ``BrokerStats`` records sent
over frames sent."""
from bench.stats import delta


def read(run):
    frames = delta(run.begin, run.end, "frames_sent")
    return delta(run.begin, run.end, "sent") / frames if frames else None
