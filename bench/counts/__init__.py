"""Operations and bytes an algorithm needs, computed from shapes. One
module per kernel or step; each is exact arithmetic on the shapes alone."""
