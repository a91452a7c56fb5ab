from repro.train.monitors import LossCurveMonitor, StepTimeMonitor

__all__ = ["LossCurveMonitor", "StepTimeMonitor"]
