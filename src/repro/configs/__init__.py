"""The paper's experiment presets (:mod:`repro.configs.paper_auction`)."""
