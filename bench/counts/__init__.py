"""Operations that each model family needs, from its sizes."""
