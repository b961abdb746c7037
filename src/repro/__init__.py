"""repro: reproduction of "Communication Optimization for Distributed
Training" — models, CCL, network, scheduler, and codesign layers."""
