"""Operations and bytes of the benchmark's models, from their shapes alone:
fixed by the model, whatever implements it."""
