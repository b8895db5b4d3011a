from repro.training.trainer import (AsyncByzantineTrainer, ByzantineTrainer,
                                    init_flat_agg_state,
                                    init_flat_async_state,
                                    make_async_byzantine_step,
                                    make_byzantine_step)

__all__ = ["AsyncByzantineTrainer", "ByzantineTrainer",
           "init_flat_agg_state", "init_flat_async_state",
           "make_async_byzantine_step", "make_byzantine_step"]
