"""Launch layer: mesh construction, roofline analysis, the serving entry
point, the perf gate and the compile cache."""
