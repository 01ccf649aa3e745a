"""Partitioner configurations: :mod:`repro.configs.parrsb` holds the
pipeline presets and :class:`~repro.configs.parrsb.ParRSBConfig`."""
