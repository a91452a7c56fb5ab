from repro.data.pipeline import curve_dataset

__all__ = ["curve_dataset"]
